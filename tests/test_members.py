"""Per-instance link fabric (DESIGN.md §10).

Two contracts anchor the refactor:

* PARITY — with uniform healthy members, every plan, ``plan_signature()``
  and simulated timing is BIT-identical to the class-level (memberless)
  model: the member dimension must cost nothing until instances diverge.
* DRAIN — with one NIC rail degraded, Stage 2 converges to a plan where
  only that member's share is reduced; its siblings stay within one
  member-grid unit of their healthy shares and the CLASS share vector
  does not move (the hold rule).
"""

import dataclasses
import json

import pytest

from hypothesis import given, settings, strategies as st

from repro.cluster.topology import (degrade_cluster, make_cluster,
                                    make_nic_tier)
from repro.control import (DegradedTimingSource, MEMBER_BASE,
                           MeasuredTimingSource, SlotController,
                           TuningProfile)
from repro.core.communicator import CommConfig, FlexCommunicator
from repro.core.links import (LinkKind, LinkMember, LinkSpec, PROFILES,
                              degrade_profile, degraded_profile_name,
                              idle_bw_opportunity, parse_degrade,
                              register_profile, split_by_health)
from repro.core.routing import build_plan, canonical_member_layout
from repro.core.simulator import MiB, PathTimingModel
from repro.core.topology import Collective
from repro.core.tuner import SHARE_GRID, initial_tune, measure_fn

AR = Collective.ALL_REDUCE
AG = Collective.ALL_GATHER


def _membered(profile, link_name, n):
    """A copy of ``profile`` whose ``link_name`` carries n uniform healthy
    members — the parity construction.  The name is kept: the h800
    primary calibration is keyed on it, and these copies are fed straight
    to PathTimingModel, never registered."""
    links = tuple(
        l.with_members([f"{l.name}.{i}" for i in range(n)])
        if l.name == link_name else l for l in profile.links)
    return dataclasses.replace(profile, links=links)


def _nic8(name="members_h800_rail8"):
    return make_cluster("h800", 2, nics_per_node=8, nic_gbit=400.0,
                        name=name)


# ---------------------------------------------------------------------------
# parity: uniform healthy members == class-level model, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("base,link", [("h800", "rdma"), ("tpu_v5e", "dcn")])
@pytest.mark.parametrize("n_members", [2, 4, 8])
def test_parity_intra_timing_bitwise(base, link, n_members):
    prof = PROFILES[base]
    memb = _membered(prof, link, n_members)
    m0, m1 = PathTimingModel(prof), PathTimingModel(memb)
    paths = [l.name for l in prof.links]
    for op in (AR, AG):
        for mib in (1, 32, 256):
            for shares in ({p: 1.0 / len(paths) for p in paths},
                           {paths[0]: 0.7, link: 0.3}):
                a = m0.measure(op, 8, mib * MiB, shares)
                b = m1.measure(op, 8, mib * MiB, shares)
                assert a == b, (op, mib, shares)
                assert (m0.total_time(op, 8, mib * MiB, shares)
                        == m1.total_time(op, 8, mib * MiB, shares))


def test_parity_inter_tier_timing_and_stage1_bitwise():
    """The NIC tier ships WITH per-rail members now; a memberless clone is
    the pre-refactor model, and healthy they must be indistinguishable."""
    nic = _nic8().nic_tier
    flat = dataclasses.replace(
        nic, name=nic.name + ":flat",
        links=tuple(dataclasses.replace(l, members=()) for l in nic.links))
    m_memb, m_flat = PathTimingModel(nic), PathTimingModel(flat)
    paths = [l.name for l in nic.links]
    for op in (AR, AG):
        for mib in (4, 64, 256):
            res_m = initial_tune(paths, "rail",
                                 measure_fn(m_memb, op, 2, mib * MiB))
            res_f = initial_tune(paths, "rail",
                                 measure_fn(m_flat, op, 2, mib * MiB))
            assert res_m.shares == res_f.shares
            assert res_m.iterations == res_f.iterations
            fr = res_m.fractions()
            assert (m_memb.measure(op, 2, mib * MiB, fr)
                    == m_flat.measure(op, 2, mib * MiB, fr))


def test_parity_plan_signature_bitwise():
    """Communicator-level: tuned plans + signatures of the membered NIC
    tier equal the memberless clone's, slot for slot."""
    nic = _nic8().nic_tier
    flat = register_profile(dataclasses.replace(
        nic, name=nic.name + ":flatsig",
        links=tuple(dataclasses.replace(l, members=()) for l in nic.links)))
    c_m = FlexCommunicator("node", 2, CommConfig(profile=nic.name))
    c_f = FlexCommunicator("node", 2, CommConfig(profile=flat.name))
    for comm in (c_m, c_f):
        for op in (AR, AG):
            for nbytes in (1 << 20, 64 << 20, 256 << 20):
                comm._bucket_plan(op, nbytes)
    assert c_m.plan_signature() == c_f.plan_signature()
    for op in (AR, AG):
        pm = c_m._bucket_plan(op, 64 << 20)
        assert pm.member_layout == ()
        assert pm == c_f._bucket_plan(op, 64 << 20)


def test_parity_with_noise_same_rng_stream():
    """The uniform fast path must not consume extra rng draws: noisy
    timings match the memberless model draw for draw."""
    prof = PROFILES["h800"]
    memb = _membered(prof, "pcie", 4)
    m0 = PathTimingModel(prof, noise=0.05, seed=7)
    m1 = PathTimingModel(memb, noise=0.05, seed=7)
    shares = {"nvlink": 0.6, "pcie": 0.25, "rdma": 0.15}
    for _ in range(20):
        assert (m0.measure(AR, 8, 64 * MiB, shares)
                == m1.measure(AR, 8, 64 * MiB, shares))


@settings(max_examples=40, deadline=None)
@given(n_members=st.integers(2, 8),
       units=st.lists(st.integers(0, 40), min_size=3, max_size=3),
       op=st.sampled_from([AR, AG]))
def test_uniform_member_plans_match_class_plans(n_members, units, op):
    """Property: ANY share vector builds the same plan with a uniform
    member layout as with none — signature for signature."""
    shares = {"primary": units[0], "staged": units[1], "ortho": units[2]}
    if sum(units) == 0:
        shares = None
    layout = {"staged": tuple((f"m{i}", 5) for i in range(n_members))}
    a = build_plan(op, "x", shares, "y")
    b = build_plan(op, "x", shares, "y", member_layout=layout)
    assert a == b
    assert b.member_layout == ()


def test_canonical_member_layout_rules():
    units = {"primary": 10, "staged": 6}
    # gcd-normalization: scaled vectors are the same identity
    a = canonical_member_layout(
        {"primary": (("r0", 8), ("r1", 2))}, units)
    b = canonical_member_layout(
        {"primary": (("r0", 16), ("r1", 4))}, units)
    assert a == b == (("primary", (("r0", 4), ("r1", 1))),)
    # a zero-weight member is a live drain, not a shorter uniform vector
    z = canonical_member_layout(
        {"primary": (("r0", 3), ("r1", 3), ("r2", 0))}, units)
    assert z == (("primary", (("r0", 1), ("r1", 1), ("r2", 0))),)
    # classes carrying no payload drop out
    assert canonical_member_layout(
        {"ortho": (("r0", 2), ("r1", 1))}, units) == ()


# ---------------------------------------------------------------------------
# drain: one degraded rail, Stage 2, the acceptance trajectory
# ---------------------------------------------------------------------------

def _degraded_nic8():
    cl = _nic8("members_h800_rail8_d")
    return cl.nic_tier, degrade_cluster(cl, "rail3=0.25").nic_tier


def test_stage2_drains_only_the_sick_member():
    healthy, degraded = _degraded_nic8()
    mh = PathTimingModel(healthy)
    md = PathTimingModel(degraded)
    res = initial_tune(["rail", "xrail", "host_tcp"], "rail",
                       measure_fn(mh, AR, 2, 256 * MiB))
    uniform = {"rail": {m.name: MEMBER_BASE for m in
                        degraded.link("rail").members}}
    sc = SlotController.warm_start(
        AR, 256 << 20, dict(res.shares), "rail",
        members=degraded.multi_member_links(), member_weights=uniform)
    for _ in range(400):
        t = md.measure(AR, 2, 256 * MiB, sc.fractions(),
                       member_weights=sc.member_weights())
        sc.report(t)
    weights = sc.member_weights()["rail"]
    rail3 = weights.pop("rail3")
    siblings = list(weights.values())
    # only the sick member drained; siblings within 1 grid unit of their
    # healthy (equal) share
    assert rail3 < min(siblings)
    assert all(abs(w - MEMBER_BASE) <= 1 for w in siblings)
    # the hold rule kept the CLASS share vector untouched
    assert sc.shares == res.shares
    assert len(sc.balancer.adjustments) == 0
    assert sum(len(b.adjustments) for b in sc.member_balancers.values()) > 0


def test_drain_rekeys_plan_and_signature_via_communicator():
    """End to end through record_call: a warm-started slot with uniform
    weights on the degraded fabric drains, the plan's member_layout goes
    non-uniform, and observe_executed_step reports the re-key."""
    _, degraded = _degraded_nic8()
    register_profile(degraded)
    comm = FlexCommunicator("node", 2, CommConfig(profile=degraded.name))
    sc = comm.slot(AR, 256 << 20)
    # reset the health-aware start to the uniform (healthy-believed) split
    for bal in sc.member_balancers.values():
        for k in bal.shares:
            bal.shares[k] = MEMBER_BASE
    plan0 = comm._bucket_plan(AR, 256 << 20)
    assert plan0.member_layout == ()
    sig0 = comm.plan_signature()
    moved = False
    for _ in range(400):
        if comm.observe_executed_step():
            moved = True
        comm._default_recorder.record(AR, 256 << 20)
    assert moved
    plan1 = comm._bucket_plan(AR, 256 << 20)
    assert plan1.member_layout != ()
    assert dict(plan1.member_layout)["primary"] is not None
    assert comm.plan_signature() != sig0
    # the drain re-keys the plan ONCE at its settled endpoint (plan
    # weights are frozen while the intra-class gap is live), not once per
    # unit move — re-jitting byte-identical HLO ~6 times per episode
    assert 1 <= comm.plan_cache.stats.retraces <= 2
    weights = sc.member_weights()["rail"]
    assert weights["rail3"] < min(v for k, v in weights.items()
                                  if k != "rail3")
    rep = comm.report()
    blk = rep[f"{AR.value}@{256 << 20}"]
    assert blk["members"]["rail"]["health"]["rail3"] == 0.25
    assert rep["rollup"]["inter"]["drained_members"] >= 1


def test_stage1_level_drain_on_degraded_profile():
    """A cold tune on the degraded fabric starts the sick member
    pre-drained (health-proportional weights) — what the dryrun CI smoke
    observes without running Stage 2."""
    _, degraded = _degraded_nic8()
    register_profile(degraded)
    comm = FlexCommunicator("node", 2, CommConfig(profile=degraded.name))
    sc = comm.slot(AG, 64 << 20)
    w = sc.member_weights()["rail"]
    assert w["rail3"] < min(v for k, v in w.items() if k != "rail3")
    assert all(abs(v - MEMBER_BASE) <= 1 for k, v in w.items()
               if k != "rail3")
    assert comm._bucket_plan(AG, 64 << 20).member_layout != ()


# ---------------------------------------------------------------------------
# register_profile contracts under the member model
# ---------------------------------------------------------------------------

def test_register_synthesized_rail_tier_idempotent():
    a = make_nic_tier(PROFILES["h800"], nics_per_node=8, nic_gbit=400.0)
    b = make_nic_tier(PROFILES["h800"], nics_per_node=8, nic_gbit=400.0)
    assert a == b
    r1 = register_profile(a)
    r2 = register_profile(b)
    assert r1 is r2
    assert len(r1.link("rail").members) == 8


def test_register_conflicting_member_layout_raises():
    a = make_nic_tier(PROFILES["a800"], nics_per_node=4, nic_gbit=400.0)
    register_profile(a)
    conflict = dataclasses.replace(
        a, links=(a.links[0].degraded("rail1", 0.5),) + a.links[1:])
    with pytest.raises(ValueError, match="different parameters"):
        register_profile(conflict)


def test_register_rejects_colliding_member_names():
    nic = _nic8("members_collide").nic_tier
    # a member named after a sibling link cross-wires timing dicts
    bad_member = dataclasses.replace(
        nic, name="members_collide_a",
        links=(nic.links[0].with_members(
            ["rail0", "rail1", "rail2", "xrail",
             "rail4", "rail5", "rail6", "rail7"]),) + nic.links[1:])
    with pytest.raises(ValueError, match="collides with a link name"):
        register_profile(bad_member)
    # two links sharing a member name is ambiguous instance addressing
    dup = dataclasses.replace(
        nic, name="members_collide_b",
        links=(nic.links[0],
               nic.links[1].with_members(["rail0", "x1"]),
               nic.links[2]))
    with pytest.raises(ValueError, match="appears in links"):
        register_profile(dup)
    # the allowed shadowing: a degraded memberless link materializes its
    # single self-named member
    ok = degrade_profile(PROFILES["gb300"], "rdma=0.5", register=False)
    register_profile(ok)
    # a duplicate WITHIN one link conflates two physical instances (and
    # silently loses split_by_health units) — rejected too
    same = dataclasses.replace(
        nic, name="members_collide_c",
        links=(nic.links[0].with_members(
            ["rail0", "rail0", "rail2", "rail3",
             "rail4", "rail5", "rail6", "rail7"]),) + nic.links[1:])
    with pytest.raises(ValueError, match="twice"):
        register_profile(same)


def test_dead_member_prices_as_inf_not_crash():
    """factor=0 is a legal spec (a dead rail): the analytics must price
    it as unusable, not raise ZeroDivisionError."""
    from repro.cluster import ClusterTimingModel
    cl = _nic8("members_h800_rail8_z")
    dead_rail = degrade_cluster(cl, "rail3=0")
    model = ClusterTimingModel(dead_rail, 8)
    assert model.flat_time(AR, MiB) == float("inf")
    assert model.algbw_GBps(AR, MiB, schedule="flat") == 0.0
    # hierarchical still works: the NIC tier routes around the dead rail
    assert model.hierarchical_time(AR, MiB) < float("inf")
    # a dead PRIMARY makes the idle-BW ratio infinite, not a crash
    d = degrade_profile(PROFILES["h800"], "nvlink=0", register=False)
    assert idle_bw_opportunity(d) == float("inf")


def test_degraded_profile_names_are_deterministic_and_distinct():
    nic = _nic8("members_h800_rail8_n").nic_tier
    d1 = degrade_profile(nic, "rail3=0.25")
    d2 = degrade_profile(nic, "rail3=0.25")
    assert d1 is d2                       # registered once, resolved again
    assert d1.name == degraded_profile_name(nic.name, "rail", "rail3", 0.25)
    assert d1.name != nic.name
    with pytest.raises(ValueError, match="different parameters"):
        register_profile(dataclasses.replace(nic, name=d1.name))


# ---------------------------------------------------------------------------
# idle_bw_opportunity — first direct unit tests (+ degraded members)
# ---------------------------------------------------------------------------

def test_idle_bw_paper_rows():
    # Table-1 reproduction, via the hardware DB (benchmarks/table1_idle_bw)
    paper = {"h800": 32, "h100": 14, "a800": 16, "gb200": 22, "gb300": 33}
    for name, pct in paper.items():
        got = idle_bw_opportunity(PROFILES[name]) * 100
        assert abs(got - pct) <= 1.5, (name, got, pct)


def test_idle_bw_gb300_no_contention_row():
    """GB300 decouples the IO paths: the opportunity is the plain sum of
    secondary raw bandwidths over NVLink — no PCIe ceiling involved."""
    p = PROFILES["gb300"]
    assert p.pcie_switch_ceiling_GBps is None
    assert not any(l.shares_pcie_switch for l in p.secondary)
    expect = sum(l.raw_GBps for l in p.secondary) / p.primary.raw_GBps
    assert idle_bw_opportunity(p) == pytest.approx(expect)
    # degrading a secondary member shrinks the opportunity proportionally
    d = degrade_profile(p, "rdma=0.5", register=False)
    lost = 0.5 * p.link("rdma").raw_GBps / p.primary.raw_GBps
    assert idle_bw_opportunity(d) == pytest.approx(expect - lost)


def test_idle_bw_degraded_member_shrinks_opportunity():
    """A degraded SECONDARY member shrinks the reported opportunity by
    exactly its lost raw-bandwidth slice (uncontended link, so no ceiling
    masks it); a degraded PRIMARY member shrinks the denominator, raising
    the ratio — both directions follow from health-scaling the raws."""
    from repro.core.links import NodeProfile
    prof = NodeProfile(name="idle_member_test", links=(
        LinkSpec("nv", LinkKind.NVLINK, raw_GBps=400.0,
                 effective_GBps=139.0, step_latency_us=4.0),
        LinkSpec("nic", LinkKind.RDMA, raw_GBps=100.0,
                 effective_GBps=40.0, step_latency_us=10.0).with_members(
                     ["nic0", "nic1", "nic2", "nic3"]),
    ))
    base = idle_bw_opportunity(prof)
    assert base == pytest.approx(100.0 / 400.0)
    d = dataclasses.replace(
        prof, links=(prof.links[0],
                     prof.links[1].degraded("nic3", 0.25)))
    # nic3's lost 3/4 of its 25 GB/s slice: 100 -> 81.25 over 400
    assert idle_bw_opportunity(d) == pytest.approx(81.25 / 400.0)
    # primary-member degradation shrinks the denominator instead
    nic = _nic8("members_h800_rail8_i").nic_tier
    dp = dataclasses.replace(
        nic, links=(nic.links[0].degraded("rail3", 0.25),) + nic.links[1:])
    assert dp.link("rail").health_factor == pytest.approx((7 + 0.25) / 8)
    assert idle_bw_opportunity(dp) > idle_bw_opportunity(nic)


def test_split_by_health_exact_and_deterministic():
    mems = tuple(LinkMember(f"r{i}") for i in range(8))
    assert split_by_health(mems, 64) == {f"r{i}": 8 for i in range(8)}
    degraded = tuple(
        dataclasses.replace(m, health=0.25 if m.name == "r3" else 1.0)
        for m in mems)
    w = split_by_health(degraded, 64)
    assert sum(w.values()) == 64
    assert w["r3"] < min(v for k, v in w.items() if k != "r3")


# ---------------------------------------------------------------------------
# TuningProfile: per-instance entries round-trip
# ---------------------------------------------------------------------------

def test_tuning_profile_member_roundtrip(tmp_path):
    path = str(tmp_path / "t.json")
    prof = TuningProfile(path)
    members = {"rail": {"rail0": 9, "rail1": 9, "rail2": 9, "rail3": 2,
                        "rail4": 9, "rail5": 9, "rail6": 9, "rail7": 8}}
    prof.record("p", "ring", AR, 2, 1 << 20, SHARE_GRID,
                {"rail": 60, "xrail": 40}, members=members)
    prof.record("p", "ring", AG, 2, 1 << 20, SHARE_GRID,
                {"rail": 70, "xrail": 30})          # member-less entry
    prof.save()
    back = TuningProfile.load(path)
    assert back.lookup_members("p", "ring", AR, 2, 1 << 20,
                               SHARE_GRID) == members
    assert back.lookup_members("p", "ring", AG, 2, 1 << 20,
                               SHARE_GRID) is None
    # corrupt members block degrades to None, not a crash
    with open(path) as f:
        doc = json.load(f)
    ar_entry, = [e for e in doc["entries"] if e["op"] == AR.value]
    ar_entry["members"] = "garbage"
    with open(path, "w") as f:
        json.dump(doc, f)
    again = TuningProfile.load(path)
    assert again.lookup_members("p", "ring", AR, 2, 1 << 20,
                                SHARE_GRID) is None


def test_warm_start_restores_saved_member_weights():
    drained = {"rail0": 9, "rail1": 9, "rail2": 9, "rail3": 2,
               "rail4": 9, "rail5": 9, "rail6": 9, "rail7": 8}
    nic = _nic8("members_h800_rail8_w").nic_tier
    sc = SlotController.warm_start(
        AR, 1 << 20, {"rail": 60, "xrail": 40, "host_tcp": 0}, "rail",
        members=nic.multi_member_links(),
        member_weights={"rail": drained})
    assert sc.member_weights()["rail"] == drained
    # mismatched member names fall back to the health split
    sc2 = SlotController.warm_start(
        AR, 1 << 20, {"rail": 60, "xrail": 40, "host_tcp": 0}, "rail",
        members=nic.multi_member_links(),
        member_weights={"rail": {"bogus": 64}})
    assert sc2.member_weights()["rail"] == {
        f"rail{i}": MEMBER_BASE for i in range(8)}


# ---------------------------------------------------------------------------
# degrade spec parsing + cluster resolution
# ---------------------------------------------------------------------------

def test_parse_degrade_forms():
    assert parse_degrade("rail3=0.25") == ("rail3", None, 0.25)
    assert parse_degrade("rail:rail3=0.25") == ("rail", "rail3", 0.25)
    assert parse_degrade("pcie=0.5") == ("pcie", None, 0.5)
    for bad in ("rail3", "=0.5", "a=b", "a=-1", ":m=0.5", "l:=0.5"):
        with pytest.raises(ValueError):
            parse_degrade(bad)


def test_degrade_cluster_targets_the_owning_tier():
    cl = _nic8("members_h800_rail8_c")
    d_rail = degrade_cluster(cl, "rail3=0.25")
    assert d_rail.node == cl.node
    assert d_rail.nic_tier.link("rail").member("rail3").health == 0.25
    assert "!rail:rail3=0.25" in d_rail.nic_tier.name
    d_pcie = degrade_cluster(cl, "pcie=0.5")
    assert d_pcie.nic_tier == cl.nic_tier
    assert d_pcie.node.link("pcie").health_factor == 0.5
    with pytest.raises(KeyError):
        degrade_cluster(cl, "nosuch=0.5")


# ---------------------------------------------------------------------------
# DegradedTimingSource — measured-mode fault overlay
# ---------------------------------------------------------------------------

def test_degraded_timing_source_overlays_member_entries():
    _, degraded = _degraded_nic8()
    model = PathTimingModel(degraded)
    src = DegradedTimingSource(MeasuredTimingSource(model))
    assert src.kind == "measured"
    fr = {"rail": 0.6, "xrail": 0.4, "host_tcp": 0.0}
    weights = {"rail": {f"rail{i}": MEMBER_BASE for i in range(8)}}
    t = src.timings_for(AR, 2, 64 << 20, fr, bucket=64 << 20,
                        member_weights=weights)
    # class entries from the measured source, member entries overlaid
    assert set(fr) <= set(t)
    assert {f"rail{i}" for i in range(8)} <= set(t)
    assert t["rail3"] > t["rail0"]        # the sick rail reads slow
    assert src.report()["degraded_overlay"] is True


def test_communicator_wraps_measured_source_on_degraded_profile():
    _, degraded = _degraded_nic8()
    register_profile(degraded)
    c = FlexCommunicator("node", 2, CommConfig(profile=degraded.name,
                                               timing="measured"))
    assert isinstance(c.timing, DegradedTimingSource)
    assert c.timing.kind == "measured"
    healthy = _nic8("members_h800_rail8_hm").nic_tier
    register_profile(healthy)
    c2 = FlexCommunicator("node", 2, CommConfig(profile=healthy.name,
                                                timing="measured"))
    assert isinstance(c2.timing, MeasuredTimingSource)
