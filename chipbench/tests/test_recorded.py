"""Named scopes and the program's phase spans on a recorded trace of one
v5e chip (``record_scopes.py``): four ticks of the serving engine at the
CPU tests' size, each inside a ``bench.tick`` annotation, with the
compiled HLO text of the step at each of its three buckets."""

import gzip
from pathlib import Path

import pytest

from chipbench import scopes as S, trace as T

DATA = Path(__file__).parent / "data"
TICK_SPANS = ["serve.plan", "serve.pack", "serve.issue", "serve.await",
              "serve.fetch", "serve.sample", "serve.commit"]


@pytest.fixture(scope="module")
def recorded():
    trace = T.load(str(DATA / "scoped_ticks.xplane.pb"),
                   host_prefixes=("bench.", "serve."))
    texts = [gzip.open(p, "rt").read() for p in
             sorted(DATA.glob("scoped_ticks.*.hlo.txt.gz"))]
    return trace, texts


def test_each_tick_holds_the_seven_phases_in_order(recorded):
    trace, _ = recorded
    ticks = [s for s in trace.host if s.name == "bench.tick"]
    assert len(ticks) == 4
    for tick in ticks:
        inside = [s.name for s in trace.host if s.name.startswith("serve.")
                  and tick.start <= s.start < tick.end]
        assert inside == TICK_SPANS


def test_device_ops_take_the_steps_scopes(recorded):
    """The trace's instruction names and shapes match the chip's compiled
    text: nearly all device time gets a scope, and the ``attn`` part of
    it is a real share."""
    trace, texts = recorded
    assert len(texts) == 3
    lo, hi = trace.host[0].start, trace.host[-1].end
    scopes = S.assign(trace, texts)
    rows = dict(S.breakdown(trace, scopes, lo, hi))
    busy = sum(rows.values())
    assert busy > 0
    assert rows.get(S.AMBIGUOUS, 0.0) == 0.0
    assert rows.get(S.UNSCOPED, 0.0) < 0.1 * busy
    assert {"layers/attn/kv_gather", "layers/attn/attend", "layers/mlp",
            "head"} <= set(rows)
    assert 0.0 < S.share(trace, scopes, lo, hi, "attn") < 1.0
