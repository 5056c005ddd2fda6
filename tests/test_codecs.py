"""Compressed collectives on secondary paths (DESIGN.md §12): codec
registry + spec parsing, Pallas encode/decode kernel roundtrips vs the
reference oracles, tuner-priced codec choice, the frozen no-codec parity
contract (golden Stage-1 trajectories and plan signatures), compressed
cold->warm tuning-cache restore, codec-aware roofline terms, and the
fp8 + error-feedback train-smoke loss equivalence.

Parity discipline: the golden numbers below were captured from the
pre-codec simulator — every uncompressed call must keep reproducing them
EXACTLY (``==`` on floats, not approx), because the default path is
contractually byte-identical: same float ops in the same order.
"""

import json
import os
import tempfile
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.codecs import (BF16_PACK, FP8_E4M3, PayloadCodec,
                               canonical_spec, codecs_for_pricing,
                               get_codec, lossy_codec_name, parse_compress)
from repro.core.communicator import (CommConfig, comm_destroy_all,
                                     comm_init_rank)
from repro.core.simulator import PathTimingModel
from repro.core.topology import Collective
from repro.core.tuner import initial_tune, measure_fn
from repro.kernels import ops, ref

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 CPU devices")

AR = Collective.ALL_REDUCE
AG = Collective.ALL_GATHER
MiB = 2 ** 20


@pytest.fixture(autouse=True)
def _fresh_comms():
    comm_destroy_all()
    yield
    comm_destroy_all()


# ---------------------------------------------------------------------------
# registry + spec parsing
# ---------------------------------------------------------------------------

def test_registry_and_aliases():
    assert get_codec("bf16") is BF16_PACK
    assert get_codec("fp8") is FP8_E4M3
    assert get_codec("bf16_pack").lossless
    assert not get_codec("fp8_e5m2").lossless
    # losslessness is per payload dtype: the pack is bit-exact for bf16
    # data but truncates fp32 mantissas (the EF gate must see that)
    assert get_codec("bf16_pack").lossless_for("bfloat16")
    assert not get_codec("bf16_pack").lossless_for("float32")
    assert get_codec("off").lossless_for("float32")
    assert not get_codec("fp8").lossless_for("float32")
    # wire math: bf16 halves; fp8 ships 1B values + 4B/128-lane-row scales
    assert get_codec("bf16").wire_bytes(1024) == 512
    assert get_codec("fp8").wire_ratio == pytest.approx((1 + 4 / 128) / 4)
    # codec_time_s includes the fixed setup term, so tiny payloads are
    # dominated by it (the "never compress tiny messages" lever)
    c = get_codec("fp8")
    assert c.codec_time_s(0) == pytest.approx(c.setup_s)


def test_parse_compress_and_canonical():
    assert parse_compress("") == {}
    assert parse_compress("secondary=fp8") == {
        "staged": "fp8_e4m3", "ortho": "fp8_e4m3"}
    assert parse_compress("staged=bf16,ortho=fp8_e5m2") == {
        "staged": "bf16_pack", "ortho": "fp8_e5m2"}
    # canonical form is sorted + normalized: order/aliases never make two
    # equal configs key different tuning entries
    assert (canonical_spec("ortho=fp8,staged=bf16")
            == canonical_spec("staged=bf16_pack,ortho=fp8_e4m3"))
    assert lossy_codec_name("secondary=fp8") == "fp8_e4m3"
    # the EF gate quotes fp32 payloads by default (the pricing dtype):
    # packing fp32 gradients to bf16 LOSES bits, so it needs residuals —
    # only genuinely-bf16 trees may skip the EF state
    assert lossy_codec_name("secondary=bf16") == "bf16_pack"
    assert lossy_codec_name("secondary=bf16", payload_dtype="bfloat16") == ""
    assert lossy_codec_name("") == ""
    with pytest.raises(ValueError):
        parse_compress("primary=fp8")        # primary never compresses
    with pytest.raises(ValueError):
        parse_compress("staged=zstd")        # unknown codec
    with pytest.raises(ValueError):
        parse_compress("nonsense")


def test_codecs_for_pricing_skips_primary():
    m = PathTimingModel("h800")
    route_of = {"nvlink": "staged", "pcie": "staged", "rdma": "staged"}
    cands = codecs_for_pricing("secondary=fp8", route_of, "nvlink")
    assert set(cands) == {"pcie", "rdma"}
    assert all(c.name == "fp8_e4m3" for c in cands.values())


# ---------------------------------------------------------------------------
# kernel roundtrips vs reference oracles
# ---------------------------------------------------------------------------

def _payload(seed, shape=(33, 200), scale=3.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def test_bf16_pack_roundtrip_bit_exact_on_bf16_data():
    # bf16-origin payloads (fp32 grads that are exactly bf16-representable)
    # must survive the pack wire bit-exactly — the lossless contract
    x = _payload(0).astype(jnp.bfloat16).astype(jnp.float32)
    vals, scales = ops.wire_encode(x, codec_name="bf16_pack")
    assert scales is None
    assert vals.dtype == jnp.bfloat16
    out = ops.wire_decode(vals, scales, codec_name="bf16_pack",
                          shape=x.shape, dtype=x.dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("codec,tol", [("fp8_e4m3", 0.07),
                                       ("fp8_e5m2", 0.14)])
def test_fp8_roundtrip_error_bounded(codec, tol):
    # e4m3 keeps 3 mantissa bits (rel step 2^-4), e5m2 keeps 2 (2^-3);
    # with per-row amax scaling the roundtrip error per element is
    # bounded by half a step of the row amax
    x = _payload(1)
    out = ops.wire_roundtrip(x, codec_name=codec)
    err = np.abs(np.asarray(out) - np.asarray(x))
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert (err / amax).max() < tol
    # and the lossless codec is exact on the same data when it fits
    exact = ops.wire_roundtrip(x.astype(jnp.bfloat16).astype(jnp.float32),
                               codec_name="bf16_pack")
    assert np.asarray(exact).dtype == np.float32


@pytest.mark.parametrize("codec", ["bf16_pack", "fp8_e4m3", "fp8_e5m2"])
def test_wire_kernels_match_reference(codec):
    # canonical wire layout: 128-lane 2D (what wire_encode reshapes to)
    x = np.asarray(_payload(2, shape=(16, 128)))
    vals, scales = ops.wire_encode(jnp.asarray(x), codec_name=codec)
    if codec == "bf16_pack":
        want = ref.bf16_pack_ref(x)
        np.testing.assert_array_equal(np.asarray(vals), want)
    else:
        wvals, wscales = ref.fp8_encode_ref(jnp.asarray(x), fmt=codec)
        np.testing.assert_array_equal(
            np.asarray(vals).astype(np.float32),
            wvals.astype(np.float32))
        np.testing.assert_allclose(np.asarray(scales), wscales,
                                   rtol=1e-6)
        # fused decode+accumulate == decode then add, vs the oracle
        acc = np.asarray(_payload(3, shape=x.shape))
        got = ops.wire_decode_accumulate(vals, scales, jnp.asarray(acc),
                                         codec_name=codec)
        want_sum = ref.fp8_decode_accumulate_ref(wvals, wscales, acc)
        np.testing.assert_allclose(np.asarray(got), want_sum,
                                   rtol=1e-5, atol=1e-5)


def test_wire_roundtrip_padding_safe():
    # odd shapes exercise the lane/sublane padding path end-to-end
    for shape in [(1, 1), (7,), (5, 129), (3, 2, 67)]:
        x = _payload(4, shape=shape)
        out = ops.wire_roundtrip(x, codec_name="fp8_e4m3")
        assert out.shape == x.shape and out.dtype == x.dtype


# ---------------------------------------------------------------------------
# codec collective gradients: straight-through VJPs match the raw ring
# ---------------------------------------------------------------------------

def _mesh1d():
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:8]), ("x",))


def _grad_of(collective, codec, x):
    """d/dx of a per-rank-weighted quadratic over the collective's output.

    The per-rank weight makes the output cotangent DIFFER across ranks,
    which is what exposes a wrong all-gather transpose: selecting the own
    row BEFORE the cross-rank psum hands every rank ``sum_k g_k[k]``
    instead of ``sum_k g_k[r]``.  Payloads are small-integer fp32 (bf16-
    exact), so the compressed forward is bit-identical and only the VJP
    is under test.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core import collectives as mp

    def shard(xs):
        out = getattr(mp, collective)(xs, "x", codec=codec)
        w = lax.axis_index("x").astype(jnp.float32) + 1.0
        return jnp.sum(out * out * w)[None]

    f = shard_map(shard, mesh=_mesh1d(), in_specs=(P("x"),),
                  out_specs=P("x"), check_vma=False)
    return jax.grad(lambda xs: jnp.sum(jax.jit(f)(xs)))(x)


@needs8
@pytest.mark.parametrize("collective", ["ring_all_gather",
                                        "ring_all_reduce"])
def test_codec_collective_grads_match_uncompressed(collective):
    # integer-valued fp32 < 17 keeps every in-flight partial sum (< 8*17)
    # bf16-exact across the wire
    x = (jnp.arange(8 * 6, dtype=jnp.float32) % 17).reshape(8 * 6)
    g_plain = _grad_of(collective, "", x)
    g_codec = _grad_of(collective, "bf16_pack", x)
    assert bool(jnp.any(g_plain != 0.0))
    np.testing.assert_allclose(np.asarray(g_codec), np.asarray(g_plain),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# frozen no-codec parity: golden pre-codec simulator numbers, EXACT
# ---------------------------------------------------------------------------

def test_golden_path_time_and_measure_unchanged():
    m = PathTimingModel("h800")
    assert [l.name for l in m.profile.links] == ["nvlink", "pcie", "rdma"]
    assert m.profile.primary.name == "nvlink"
    golden_ar = {"nvlink": 0.0006782847090079817,
                 "pcie": 0.006776942769230769,
                 "rdma": 0.011554608000000001}
    golden_ag = {"nvlink": 0.003229006209855074,
                 "pcie": 0.018647771076923076,
                 "rdma": 0.034368432000000004}
    for name in golden_ar:
        assert m.path_time(name, AR, 8, 2 ** 28, 0.25) == golden_ar[name]
        assert m.path_time(name, AG, 8, 2 ** 28, 0.25) == golden_ag[name]
    fr = {"nvlink": 1 / 3, "pcie": 1 / 3, "rdma": 1 / 3}
    t = m.measure(AR, 8, 2 ** 28, fr)
    assert t == {"nvlink": 0.0008816689168336783,
                 "pcie": 0.008282590358974358,
                 "rdma": 0.014350810666666665}
    assert m.total_time(AR, 8, 2 ** 28, fr) == 0.014350810666666665
    assert m.algbw_GBps(AR, 8, 2 ** 28, fr) == 18.705246848772678


def test_golden_stage1_trajectory_unchanged():
    m = PathTimingModel("h800")
    paths = [l.name for l in m.profile.links]
    res = initial_tune(paths, m.profile.primary.name,
                       measure_fn(m, AR, 8, 2 ** 26))
    assert res.shares == {"nvlink": 100, "pcie": 0, "rdma": 0}
    assert res.iterations == 6 and res.converged
    assert len(res.trace) == 5
    assert [(t.iteration, t.slowest, t.moved) for t in res.trace[-3:]] \
        == [(3, "pcie", 4), (4, "pcie", 4), (5, "pcie", 2)]


# ---------------------------------------------------------------------------
# tuner-priced codec choice
# ---------------------------------------------------------------------------

def test_choose_codecs_size_threshold_and_primary_exclusion():
    m = PathTimingModel("h800")
    fp8 = get_codec("fp8")
    cands = {"pcie": fp8, "rdma": fp8}
    # tiny messages: the setup term dominates any wire saving
    assert m.choose_codecs(AR, 8, 4 * 1024, cands) == {}
    assert m.choose_codecs(AR, 8, 64 * 1024, cands) == {}
    # bandwidth-bound payloads: both secondary paths compress
    assert m.choose_codecs(AR, 8, 256 * MiB, cands) == {
        "pcie": "fp8_e4m3", "rdma": "fp8_e4m3"}
    # the primary NEVER compresses, even if forced into the candidates
    forced = dict(cands, nvlink=fp8)
    assert "nvlink" not in m.choose_codecs(AR, 8, 256 * MiB, forced)


def test_codec_pricing_strictly_cheaper_when_chosen():
    m = PathTimingModel("h800")
    fp8 = get_codec("fp8")
    base = m.path_time("pcie", AR, 8, 256 * MiB, 1.0)
    comp = m.path_time("pcie", AR, 8, 256 * MiB, 1.0, codec=fp8)
    assert comp < base
    # primary path ignores the codec entirely (no wire scaling, no cost)
    assert (m.path_time("nvlink", AR, 8, 256 * MiB, 1.0, codec=fp8)
            == m.path_time("nvlink", AR, 8, 256 * MiB, 1.0))


# ---------------------------------------------------------------------------
# communicator: no-codec signature parity + compressed cold->warm restore
# ---------------------------------------------------------------------------

@needs8
def test_default_comm_has_no_codecs_and_compress_changes_plans():
    base = comm_init_rank("p", 8, CommConfig(profile="h800"))
    off = comm_init_rank("p", 8, CommConfig(profile="h800", compress=""))
    assert base is off                     # same dataclass value -> memoized
    sc = base.slot(AR, 256 * MiB)
    assert sc.codecs == {}
    assert base._bucket_plan(AR, 256 * MiB).path_codecs == ()
    sig_off = base.plan_signature()

    # on a healthy h800 the AR tuner parks ~all units on NVLink, so the
    # codec choice exists but the quantized plan ships nothing on the
    # secondary paths — no codec may appear in the plan (a codec only
    # rides paths that actually carry units)
    scc = comm_init_rank("q", 8, CommConfig(profile="h800",
                                            compress="secondary=fp8"))
    assert scc.slot(AR, 256 * MiB).codecs
    qplan = scc._bucket_plan(AR, 256 * MiB)
    assert qplan.path_codecs == ()
    assert qplan.chunk_units == base._bucket_plan(AR, 256 * MiB).chunk_units

    # degrade the primary: secondary paths now carry real units, and the
    # codec ids become part of the plan (and therefore its signature)
    from repro.core.links import PROFILES, degrade_profile
    deg = degrade_profile(PROFILES["h800"], "nvlink=0.1").name
    off_d = comm_init_rank("s", 8, CommConfig(profile=deg))
    comp_d = comm_init_rank("t", 8, CommConfig(profile=deg,
                                               compress="secondary=fp8"))
    plan = comp_d._bucket_plan(AR, 256 * MiB)
    assert plan.path_codecs, plan
    assert (off_d._bucket_plan(AR, 256 * MiB).path_codecs == ())
    # the codec id re-keys the frozen signature (executable-cache key)
    import dataclasses as dc
    po = off_d.plan_signature()[0][2]
    pc = comp_d.plan_signature()[0][2]
    assert dc.replace(po, axis_name="") != dc.replace(pc, axis_name="")
    assert pc.path_codecs == (("staged", "fp8_e4m3"),)


@needs8
def test_compressed_report_breaks_out_wire_bytes():
    comm = comm_init_rank("r", 8, CommConfig(profile="h800",
                                             compress="secondary=fp8"))
    comm.slot(AR, 256 * MiB)
    comm.slot(AR, 4 * 1024)          # tiny slot: codecs must NOT activate
    rep = comm.report()
    big = rep[f"all_reduce@{256 * MiB}"]
    small = rep["all_reduce@4096"]
    assert big["codecs"] and "codecs" not in small
    w = big["wire"]
    assert w["wire_bytes"] < w["logical_bytes"]
    assert w["bytes_saved"] == w["logical_bytes"] - w["wire_bytes"]
    for p, row in w["paths"].items():
        if row["codec"] == "off":
            assert row["wire_bytes"] == row["logical_bytes"]
        else:
            assert row["wire_bytes"] < row["logical_bytes"]
    roll = rep["rollup"][comm.profile.tier]
    assert roll["compressed_slots"] == 1
    assert roll["offloaded_bytes_saved"] == w["bytes_saved"]


@needs8
def test_compressed_cold_warm_restore_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        cache = os.path.join(d, "tune.json")
        cfg = CommConfig(profile="h800", compress="secondary=fp8",
                         tuning_cache=cache)
        cold = comm_init_rank("w", 8, cfg)
        sc = cold.slot(AR, 256 * MiB)
        assert not sc.warm and sc.codecs
        cold_sig = cold.plan_signature()
        cold_shares = dict(sc.tuned.shares)
        cold_codecs = dict(sc.codecs)
        cold.save_tuning()
        with open(cache) as f:
            raw = json.load(f)
        # compressed entries key a distinct algo (never collide with the
        # uncompressed cache) and carry the codec choice
        entries = raw["entries"]
        assert all("fp8_e4m3" in e["secondary_algo"] for e in entries)
        assert all(e.get("codecs") for e in entries), entries

        comm_destroy_all()
        warm = comm_init_rank("w", 8, cfg)
        scw = warm.slot(AR, 256 * MiB)
        assert scw.warm and scw.tuned.iterations == 0
        assert scw.codecs == cold_codecs
        assert dict(scw.tuned.shares) == cold_shares
        assert warm.plan_signature() == cold_sig


def test_profile_store_distinguishes_empty_codecs_from_legacy():
    # {} is a real verdict ("refinement dropped every codec") and must
    # round-trip as {}, never collapse to the legacy "entry predates
    # codecs" None that triggers a fresh full-payload choice
    from repro.control.profile import TuningProfile
    prof = TuningProfile()
    prof.record("p", "ring+staged=fp8_e4m3", AR, 8, 1024, 100,
                {"nvlink": 100}, codecs={})
    assert prof.lookup_codecs("p", "ring+staged=fp8_e4m3", AR,
                              8, 1024, 100) == {}
    prof.record("p", "ring", AR, 8, 1024, 100, {"nvlink": 100})
    assert prof.lookup_codecs("p", "ring", AR, 8, 1024, 100) is None


@needs8
def test_warm_start_restores_refined_empty_codec_choice():
    # a cold tune whose refinement dropped EVERY codec must warm-start
    # uncompressed: the saved {} pre-seeds the codec choice, so the warm
    # path never re-runs choose_codecs (which, priced on the full
    # payload, could re-attach what the fixpoint rejected)
    with tempfile.TemporaryDirectory() as d:
        cache = os.path.join(d, "tune.json")
        cfg = CommConfig(profile="h800", compress="secondary=fp8",
                         tuning_cache=cache)
        cold = comm_init_rank("e", 8, cfg)
        bucket = 64 * 1024
        assert cold.slot(AR, bucket).codecs == {}
        cold.save_tuning()
        with open(cache) as f:
            entries = json.load(f)["entries"]
        assert any(e["codecs"] == {} for e in entries), entries

        comm_destroy_all()
        warm = comm_init_rank("e", 8, cfg)

        def boom(*a, **k):
            raise AssertionError(
                "warm start re-ran choose_codecs instead of restoring "
                "the saved (empty) choice")
        warm.model.choose_codecs = boom
        scw = warm.slot(AR, bucket)
        assert scw.warm and scw.codecs == {}
        assert warm.slot_codecs(AR, bucket) == {}


@needs8
def test_uncompressed_cache_files_unchanged_by_codec_fields():
    # a default (no --compress) save must not grow a "codecs" key — the
    # cache file format stays byte-compatible with pre-codec readers
    with tempfile.TemporaryDirectory() as d:
        cache = os.path.join(d, "tune.json")
        comm = comm_init_rank("u", 8, CommConfig(profile="h800",
                                                 tuning_cache=cache))
        comm.slot(AR, 64 * MiB)
        comm.save_tuning()
        with open(cache) as f:
            raw = f.read()
        assert "codecs" not in raw and "fp8" not in raw


# ---------------------------------------------------------------------------
# codec-aware roofline terms
# ---------------------------------------------------------------------------

def test_idle_bw_opportunity_codec_scaling():
    from repro.core.links import PROFILES, idle_bw_opportunity
    prof = PROFILES["h800"]
    base = idle_bw_opportunity(prof)
    same = idle_bw_opportunity(prof, codecs={})
    assert same == base                    # no codecs -> exact historical
    fp8 = get_codec("fp8")
    boosted = idle_bw_opportunity(
        prof, codecs={l.name: fp8 for l in prof.secondary})
    # a ~3.9x wire saving on every secondary link must strictly raise the
    # opportunity, by at most 1/wire_ratio
    assert base < boosted <= base / fp8.wire_ratio + 1e-12


def test_step_time_bounds_wire_scale():
    from repro.roofline.analytic import step_time_bounds
    base = step_time_bounds(1.0, 0.5, 0.8, n_buckets=4)
    same = step_time_bounds(1.0, 0.5, 0.8, n_buckets=4, wire_scale=1.0)
    assert same == base                    # default arithmetic untouched
    comp = step_time_bounds(1.0, 0.5, 0.8, n_buckets=4, wire_scale=0.5)
    assert comp["wire_scale"] == 0.5
    assert comp["t_step_serial"] == pytest.approx(1.0 + 0.4)
    assert comp["t_step_overlap"] <= base["t_step_overlap"]
    assert comp["exposed_comm_s"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# fp8 + error feedback: train-smoke loss equivalence
# ---------------------------------------------------------------------------

def _degraded_h800(factor: float = 0.05) -> str:
    """An h800 with the primary degraded to ``factor`` of nominal: the
    Stage-1 optimum routes real share onto the secondary paths, which is
    where the codec chooser actually attaches codecs at train-smoke
    bucket sizes."""
    from repro.core.links import PROFILES, degrade_profile
    return degrade_profile(PROFILES["h800"], f"nvlink={factor}").name


def _run_train(compress: str, steps: int = 10, *, profile: str = "h800",
               bucket_mb: float = 0.25):
    """Returns (per-step losses, max |residual| or None without EF)."""
    from repro.configs import get_config
    from repro.launch import shapes as SH
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step
    from repro.data.pipeline import make_batches
    from repro.models import init_params
    from repro.optim.adamw import AdamWConfig, init_state
    from repro.train.train_step import ef_init_residuals

    comm_destroy_all()
    cfg = get_config("glm4-9b").reduced()
    mesh = make_mesh((2, 4), ("data", "model"))
    shape = SH.InputShape("t", "train", 32, 4)
    comm = CommConfig(profile=profile, compress=compress,
                      tag=f"ef-{compress or 'off'}")
    step, ctx = build_train_step(
        cfg, mesh, comm=comm, shape=shape,
        opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=steps),
        bucket_mb=bucket_mb)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = init_state(params)
    ef = bool(ctx.ef_codec_name())
    if ef:
        opt_state = (opt_state, ef_init_residuals(params))
    batches = make_batches(cfg, seq_len=32, batch_per_shard=4, seed=7)
    losses = []
    with mesh:
        for _ in range(steps):
            params, opt_state, m = step(params, opt_state,
                                        {k: jnp.asarray(v)
                                         for k, v in next(batches).items()})
            losses.append(float(m["loss"]))
    rmax = None
    if ef:
        _, residuals = opt_state
        rmax = max(float(jnp.abs(r).max())
                   for r in jax.tree_util.tree_leaves(residuals))
    return losses, rmax


@needs8
def test_fp8_ef_train_matches_uncompressed_final_loss():
    # degraded primary + big buckets: the tuner routes real share onto
    # the secondaries and the chooser attaches fp8, so EF compensates an
    # actual wire quantization
    deg = _degraded_h800()
    base, _ = _run_train("", profile=deg, bucket_mb=8.0)
    fp8, rmax = _run_train("secondary=fp8", profile=deg, bucket_mb=8.0)
    assert rmax is not None and rmax > 0.0, "EF residuals never updated"
    assert all(np.isfinite(base)) and all(np.isfinite(fp8))
    assert base[-1] < base[0] and fp8[-1] < fp8[0]   # both learn
    # error feedback keeps the lossy run's trajectory within tolerance of
    # the uncompressed one (the §12 accuracy contract)
    assert abs(fp8[-1] - base[-1]) < 0.05 * max(abs(base[-1]), 1.0), \
        (base[-1], fp8[-1])


@needs8
def test_ef_skipped_when_every_slot_declines_the_codec():
    # healthy primary + tiny buckets: every gradient-sync slot declines
    # fp8, so the wire ships exact bytes — the per-bucket EF gate must
    # skip the roundtrip (residuals stay zero) and the trajectory must
    # match the uncompressed run, not carry a phantom-quantization
    # perturbation
    base, _ = _run_train("", steps=4)
    fp8, rmax = _run_train("secondary=fp8", steps=4)
    assert rmax == 0.0, f"EF perturbed an uncompressed transfer: {rmax}"
    np.testing.assert_allclose(fp8, base, rtol=1e-6)


@needs8
def test_bf16_on_fp32_gradients_counts_as_lossy_for_ef():
    # bf16_pack truncates fp32 mantissas: with fp32 params the EF gate
    # must pair the residual state (only genuinely-bf16 trees skip it)
    comm_destroy_all()
    from repro.models.tp import ParallelCtx
    ctx = ParallelCtx(comm_config=CommConfig(profile="h800",
                                             compress="secondary=bf16"))
    assert ctx.ef_codec_name() == "bf16_pack"
    assert ctx.ef_codec_name("bfloat16") == ""
    # bf16's 2:1 wire saving needs a harder-degraded primary than fp8's
    # ~3.9:1 before the chooser attaches it at the smoke's bucket size
    losses, rmax = _run_train("secondary=bf16", steps=4,
                              profile=_degraded_h800(0.02), bucket_mb=8.0)
    assert rmax is not None and rmax > 0.0
    assert all(np.isfinite(losses))
