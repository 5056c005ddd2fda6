"""Algorithm 1 (Stage-1 coarse tuning) unit + property tests."""

import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.simulator import MiB, PathTimingModel
from repro.core.topology import Collective
from repro.core.tuner import (SHARE_GRID, initial_tune, initialize_shares)

PATHS = ["nvlink", "pcie", "rdma"]


def make_measure(op, n, mib, profile="h800", noise=0.0, seed=0):
    model = PathTimingModel(profile, noise=noise, seed=seed)
    payload = mib * MiB
    return lambda fr: model.measure(op, n, payload, fr)


def test_initial_shares_sum_to_grid():
    s = initialize_shares(PATHS, "nvlink")
    assert sum(s.values()) == SHARE_GRID
    assert s["nvlink"] >= max(s["pcie"], s["rdma"])  # primary dominant


def test_converges_on_allgather():
    res = initial_tune(PATHS, "nvlink",
                       make_measure(Collective.ALL_GATHER, 8, 256))
    assert res.converged
    assert sum(res.shares.values()) == SHARE_GRID
    # paper Table 2: 8-GPU AllGather offloads ~12+7 % — secondary paths live.
    assert res.shares["pcie"] > 0 and res.shares["rdma"] > 0
    assert 60 <= res.shares["nvlink"] <= 95


def test_8gpu_allreduce_backs_off_to_nvlink():
    """Paper §5.3: the scheduler correctly limits diversion for 8-GPU AR."""
    res = initial_tune(PATHS, "nvlink",
                       make_measure(Collective.ALL_REDUCE, 8, 256))
    assert res.shares["nvlink"] >= 95
    assert res.shares["pcie"] + res.shares["rdma"] <= 5


def test_damping_halves_step_on_bottleneck_shift():
    # Construct an oscillating oracle: whichever path holds more share is
    # "slow" — the bottleneck flips every move, so the step must halve.
    def measure(fracs):
        return {p: f for p, f in fracs.items()}  # time == share
    res = initial_tune(["nvlink", "pcie"], "nvlink", measure)
    steps = [t.step for t in res.trace if t.moved]
    assert any(b < a for a, b in zip(steps, steps[1:])), \
        "step never halved despite bottleneck flips"


def test_path_deactivation():
    # pcie is catastrophically slow -> its share must hit 0 and deactivate.
    def measure(fracs):
        out = {}
        for p, f in fracs.items():
            out[p] = f * (1000.0 if p == "pcie" else 1.0) + 1e-6
        return out
    res = initial_tune(["nvlink", "pcie"], "nvlink", measure)
    assert res.shares["pcie"] == 0
    assert "pcie" not in res.active
    assert res.converged  # NVLink-only exit (Alg.1 line 10)


def test_balanced_timings_at_convergence():
    model = PathTimingModel("h800")
    op, n, payload = Collective.ALL_GATHER, 4, 256 * MiB
    res = initial_tune(PATHS, "nvlink",
                       lambda fr: model.measure(op, n, payload, fr))
    if len(res.active) > 1:
        t = model.measure(op, n, payload, res.fractions())
        act = [t[p] for p in res.active]
        assert (max(act) - min(act)) / min(act) < 0.25


@given(mib=st.sampled_from([32, 64, 128, 256]),
       n=st.sampled_from([2, 4, 8]),
       op=st.sampled_from([Collective.ALL_GATHER, Collective.ALL_REDUCE,
                           Collective.REDUCE_SCATTER]))
@settings(max_examples=30, deadline=None)
def test_property_shares_invariants(mib, n, op):
    res = initial_tune(PATHS, "nvlink", make_measure(op, n, mib))
    assert sum(res.shares.values()) == SHARE_GRID
    assert all(v >= 0 for v in res.shares.values())
    assert res.iterations <= 100
    # the tuned config is never slower than NVLink-only (Alg.1 would have
    # deactivated the secondaries otherwise) — allow 2% simulator slack.
    model = PathTimingModel("h800")
    flex = model.algbw_GBps(op, n, mib * MiB, res.fractions())
    nccl = model.nccl_baseline_GBps(op, n, mib * MiB)
    assert flex >= nccl * 0.98


@given(seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_property_noise_robustness(seed):
    """Tuning under measurement noise still converges to sane shares."""
    res = initial_tune(
        PATHS, "nvlink",
        make_measure(Collective.ALL_GATHER, 8, 256, noise=0.05, seed=seed))
    assert sum(res.shares.values()) == SHARE_GRID
    assert res.shares["nvlink"] >= 50
