"""The reductions of ``chipbench/phases.py``: host ms per tick by phase on
the recorded v5e trace (``record_scopes.py``), and the scheduler's stamps
of synthetic requests."""

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import phases as P, trace as T

XPLANE = str(Path(__file__).parent / "data" / "scoped_ticks.xplane.pb")


def test_phases_of_the_recorded_ticks():
    """Four ticks, one step each: every phase has a median, and the
    host's time after each step follows from the step's end on the
    device."""
    trace = T.load(XPLANE, host_prefixes=("bench.", "serve."))
    steps = P.step_spans(XPLANE)
    ticks = P.tick_phases(trace, trace.host[0].start, trace.host[-1].end)
    assert len(ticks) == len(steps) == 4
    out = P.host_phases(ticks, steps)
    assert list(out["phases_ms"]) == list(P.PHASES)
    assert all(v > 0 for v in out["phases_ms"].values())
    after = [1e3 * (t["serve.fetch"].end - s[1]) for t, s in zip(ticks, steps)]
    assert out["fetch_after_step_ms"]["max"] == pytest.approx(max(after))
    rest = [sum(1e3 * (t[p].end - t[p].start) for p in P.PHASES
                if p not in ("serve.await", "serve.fetch")) for t in ticks]
    assert out["host_ms"] == pytest.approx(
        np.median([a + r for a, r in zip(after, rest)]))
    # steps that do not pair one to a tick give the phases alone
    assert set(P.host_phases(ticks, steps[1:])) == {"ticks", "phases_ms"}


def test_prefill_of_the_scheduler_stamps():
    req = lambda admit, first, stall, packed: SimpleNamespace(  # noqa: E731
        t_admit=admit, t_first=first, stall_ticks=stall,
        prefill_ticks=packed)
    reqs = [req(0.0, 1.0, 3, 1), req(1.0, 1.5, 0, 2),
            req(2.0, None, 5, 0)]              # no first token: left out
    out = P.prefill(reqs)
    assert out["requests"] == 2
    assert out["p80_ms"] == pytest.approx(np.percentile([1000, 500], 80))
    assert out["stall_share"] == pytest.approx(3 / 6)
    assert P.prefill([]) == {"requests": 0, "p80_ms": None,
                             "stall_share": None}


def test_main_runs_the_tiny_cell_on_a_cpu(monkeypatch, tmp_path, capsys):
    """The tool end to end at the CPU size, traced: the program's phases
    and the window's requests reach its JSON (a CPU trace has no TPU
    plane, so no step spans and no device time)."""
    import json

    import jax

    from chipbench import bench
    from chipbench.tests import tiny

    cell = tiny.tiny_serve(trace_seconds=1.0)
    monkeypatch.setattr(bench, "load_cell", lambda name, root: cell)
    monkeypatch.setattr(bench, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: None)
    out = tmp_path / "phases.json"
    assert P.main(["--workload", "serve.deepseek-67b.chat", "--seed",
                   str(2 ** 33 + 5), "--seconds", "2", "--out",
                   str(out)]) == 0
    got = json.loads(out.read_text())
    assert got == json.loads(capsys.readouterr().out)
    assert got["prefill"]["requests"] > 0
    assert 0.0 <= got["prefill"]["stall_share"] < 1.0
    assert got["ticks"] > 0 and list(got["phases_ms"]) == list(P.PHASES)
    assert "fetch_after_step_ms" not in got and got["busy_s"] == []
