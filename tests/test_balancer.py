"""Stage-2 runtime balancer (Evaluator + LoadBalancer) tests."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.balancer import Evaluator, LoadBalancer
from repro.core.simulator import MiB, PathTimingModel
from repro.core.topology import Collective
from repro.core.tuner import SHARE_GRID, initial_tune

PATHS = ["nvlink", "pcie", "rdma"]


def tuned_balancer(op=Collective.ALL_GATHER, n=8, mib=256):
    model = PathTimingModel("h800")
    payload = mib * MiB
    res = initial_tune(PATHS, "nvlink",
                       lambda fr: model.measure(op, n, payload, fr))
    return model, LoadBalancer(res.shares, "nvlink")


def test_evaluator_window():
    ev = Evaluator(window=5)
    for i in range(4):
        ev.record({"a": 1.0, "b": 2.0})
    assert ev.trend(["a", "b"]) is None  # window not yet full
    ev.record({"a": 1.0, "b": 2.0})
    assert ev.trend(["a", "b"]) == {"a": 1.0, "b": 2.0}


def test_median_ignores_transient_spike():
    ev = Evaluator(window=5)
    for i in range(5):
        t = {"a": 1.0, "b": 1.0}
        if i == 2:
            t["b"] = 100.0  # one spike
        ev.record(t)
    trend = ev.trend(["a", "b"])
    assert trend["b"] == 1.0  # median unaffected


def test_no_adjustment_when_balanced():
    _, bal = tuned_balancer()
    start = dict(bal.shares)
    for _ in range(50):
        bal.observe({p: 1.0 for p in PATHS})  # perfectly balanced
    assert bal.shares == start
    assert not bal.adjustments


def test_adjusts_toward_primary_when_secondary_slows():
    _, bal = tuned_balancer()
    pcie_before = bal.shares["pcie"]
    assert pcie_before > 0
    # pcie suddenly becomes 3x slower (e.g. other designs eating PCIe, §6).
    for _ in range(60):
        bal.observe({"nvlink": 1.0, "pcie": 3.0, "rdma": 1.1})
    assert bal.shares["pcie"] < pcie_before
    # moves go to the primary link (paper: "prioritizing NVLink")
    assert all(a.target == "nvlink" for a in bal.adjustments)
    assert all(a.moved == 1 for a in bal.adjustments)  # small fixed share


def test_periodic_invocation_only():
    _, bal = tuned_balancer()
    for i in range(9):
        bal.observe({"nvlink": 1.0, "pcie": 10.0, "rdma": 1.0})
    assert not bal.adjustments          # not yet invoked (period 10)
    bal.observe({"nvlink": 1.0, "pcie": 10.0, "rdma": 1.0})
    assert len(bal.adjustments) == 1    # invoked exactly at the period


def test_closed_loop_message_size_shift():
    """Fig-5 scenario: message size changes at runtime; the balancer reshapes
    the distribution using live (simulated) timings."""
    model, bal = tuned_balancer(Collective.ALL_GATHER, 8, 256)
    op, n = Collective.ALL_GATHER, 8
    # switch to small 8 MiB messages: latency terms dominate, secondary
    # shares should shrink.
    pcie_before = bal.shares["pcie"] + bal.shares["rdma"]
    for _ in range(400):
        t = model.measure(op, n, 8 * MiB, bal.fractions())
        bal.observe(t)
    pcie_after = bal.shares["pcie"] + bal.shares["rdma"]
    assert pcie_after < pcie_before
    assert sum(bal.shares.values()) == SHARE_GRID


@given(times=st.lists(
    st.fixed_dictionaries({p: st.floats(0.1, 10.0) for p in PATHS}),
    min_size=1, max_size=120))
@settings(max_examples=30, deadline=None)
def test_property_share_conservation(times):
    _, bal = tuned_balancer()
    for t in times:
        bal.observe(t)
    assert sum(bal.shares.values()) == SHARE_GRID
    assert all(v >= 0 for v in bal.shares.values())


# ---------------------------------------------------------------------------
# _maybe_adjust target selection (regression: the old guard
# `shares.get(primary, 0) >= 0` was vacuously true, so share could be
# "moved" to a primary this balancer does not even track)
# ---------------------------------------------------------------------------

def _hammer(bal, timings, n=20):
    for _ in range(n):
        bal.observe(timings)


def test_untracked_primary_is_never_a_target():
    """A balancer over secondary paths only must route moves to the fastest
    tracked path, not conjure a share entry for the absent primary."""
    bal = LoadBalancer({"pcie": 50, "rdma": 50}, "nvlink")
    _hammer(bal, {"pcie": 5.0, "rdma": 1.0})
    assert "nvlink" not in bal.shares
    assert bal.adjustments
    assert all(a.target == "rdma" for a in bal.adjustments)
    assert sum(bal.shares.values()) == SHARE_GRID


def test_primary_reactivation_from_zero_default_on():
    """Primary share 0: by default runtime moves may re-activate it (the
    NVLink-first rule applies even from zero)."""
    bal = LoadBalancer({"nvlink": 0, "pcie": 50, "rdma": 50}, "nvlink")
    _hammer(bal, {"nvlink": 1.0, "pcie": 5.0, "rdma": 1.0})
    assert bal.adjustments
    assert bal.adjustments[0].target == "nvlink"
    assert bal.shares["nvlink"] > 0


def test_primary_reactivation_can_be_pinned_off():
    bal = LoadBalancer({"nvlink": 0, "pcie": 50, "rdma": 50}, "nvlink",
                       allow_primary_reactivation=False)
    _hammer(bal, {"nvlink": 1.0, "pcie": 5.0, "rdma": 1.0})
    assert bal.shares["nvlink"] == 0          # stays deactivated
    assert bal.adjustments
    assert all(a.target == "rdma" for a in bal.adjustments)


def test_trend_skips_sampleless_paths():
    """A path with no samples in a full window (e.g. just re-activated)
    must be skipped, not stall the whole trend (regression: trend()
    returned None, freezing Stage 2 for a full window)."""
    ev = Evaluator(window=5)
    for _ in range(5):
        ev.record({"pcie": 2.0, "rdma": 1.0})
    assert ev.trend(["nvlink", "pcie", "rdma"]) == {"pcie": 2.0, "rdma": 1.0}
    # still None while the window itself is not full
    ev2 = Evaluator(window=5)
    ev2.record({"pcie": 2.0})
    assert ev2.trend(["pcie"]) is None


def test_reactivated_primary_does_not_freeze_stage2():
    """The freeze scenario end to end: the primary holds share again (a
    reactivation) but the caller's timing feed has not started covering
    it.  The balancer must keep adjusting over the sampled paths —
    previously it froze for as long as the primary stayed sample-less."""
    bal = LoadBalancer({"nvlink": 1, "pcie": 59, "rdma": 40}, "nvlink")
    for _ in range(30):
        bal.observe({"pcie": 5.0, "rdma": 1.0})     # no nvlink samples
    assert bal.adjustments, "Stage 2 froze on the sample-less primary"
    # moves keep prioritizing the (tracked, share-holding) primary
    assert all(a.source == "pcie" and a.target == "nvlink"
               for a in bal.adjustments)
    assert sum(bal.shares.values()) == SHARE_GRID


def test_single_sampled_path_makes_no_move():
    """With <2 sampled paths there is no gap to compare — no adjustment
    (and no crash) even though more paths are active."""
    bal = LoadBalancer({"nvlink": 50, "pcie": 50}, "nvlink")
    for _ in range(30):
        bal.observe({"pcie": 5.0})                  # only one path sampled
    assert not bal.adjustments


def test_slow_primary_moves_to_fastest_secondary():
    """When the primary itself is slowest the move must go to the fastest
    path, never back to the source."""
    bal = LoadBalancer({"nvlink": 80, "pcie": 10, "rdma": 10}, "nvlink")
    _hammer(bal, {"nvlink": 9.0, "pcie": 1.0, "rdma": 3.0})
    assert bal.adjustments
    assert all(a.source == "nvlink" and a.target == "pcie"
               for a in bal.adjustments)
