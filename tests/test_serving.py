"""Continuous batching + paged KV serving (DESIGN.md §13).

Covers the PR's correctness contract end to end: host-side block
accounting (allocator round-trip, table disjointness under out-of-order
retirement), the flash-decode kernel against its dense-gather oracle
({fp32,bf16} x GQA configs, fixed anchors + hypothesis), pad-row
zero-mass / zero-block invariants, preemption-by-eviction resume, and the
headline bit-identical greedy parity between the paged engine and the
wave engine — plus the batch-shape-bucket executable-cache warmth that
makes admission-driven shape changes re-jit-free.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.communicator import comm_destroy_all
from repro.kernels import ops, ref
from repro.models import init_params, single_device_ctx
from repro.runtime.program import StepProgram
from repro.serving.engine import (PagedServeConfig, PagedServeEngine,
                                  ServeConfig, ServeEngine)
from repro.serving.paged_kv import BlockAllocator, NoFreeBlocks, PagedKVCache

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _fresh():
    comm_destroy_all()
    yield
    comm_destroy_all()


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("glm4-9b").reduced()
    return cfg, init_params(KEY, cfg)


# ---------------------------------------------------------------------------
# host-side block accounting
# ---------------------------------------------------------------------------

def test_block_allocator_roundtrip_and_lifo_reuse():
    a = BlockAllocator(4)
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [0, 1, 2, 3]
    with pytest.raises(NoFreeBlocks):
        a.alloc()
    a.free(got[2])
    assert a.alloc() == got[2]          # most recently freed reused next
    rep = a.report()
    assert rep["allocs"] == 5 and rep["frees"] == 1
    assert rep["peak_in_use"] == 4 and rep["in_use"] == 4


def test_block_allocator_rejects_double_free():
    a = BlockAllocator(2)
    b = a.alloc()
    a.free(b)
    with pytest.raises(AssertionError):
        a.free(b)


def test_block_tables_disjoint_under_out_of_order_retirement():
    kv = PagedKVCache(8, 4, 4, 4)       # 8 blocks of 4 tokens, 4 rows

    def assert_disjoint():
        owned = [kv.blocks_of(r) for r in range(4)]
        flat = [b for blks in owned for b in blks]
        assert len(flat) == len(set(flat)), f"shared blocks: {owned}"
        assert all(0 <= b < 8 for b in flat)

    kv.ensure(0, 7)                     # 2 blocks
    kv.ensure(1, 5)                     # 2 blocks
    kv.ensure(2, 9)                     # 3 blocks
    assert_disjoint()
    assert kv.tokens_capacity(2) == 12 and kv.free_tokens == 4
    freed = kv.release(1)               # retire the MIDDLE row first
    assert freed == 2 and kv.n_blocks_of(1) == 0
    kv.ensure(3, 8)                     # reuses row 1's freed blocks
    assert_disjoint()
    # growing an existing row keeps its prefix blocks attached
    before = kv.blocks_of(0)
    kv.ensure(0, 8)
    assert kv.blocks_of(0)[: len(before)] == before
    with pytest.raises(NoFreeBlocks):
        kv.ensure(0, 16)                # pool dry -> scheduler's signal
    with pytest.raises(ValueError):
        kv.ensure(2, 17)                # over the per-request cap


# ---------------------------------------------------------------------------
# flash-decode kernel vs dense block-gather oracle
# ---------------------------------------------------------------------------

def _paged_case(seed, t_rows, hq, hkv, hd, nb, bs, maxb, dtype,
                n_pads=1):
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(k1, (t_rows, hq, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(k2, (nb, bs, hkv, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(k3, (nb, bs, hkv, hd), jnp.float32).astype(dtype)
    tables = jax.random.randint(k4, (t_rows, maxb), 0, nb, jnp.int32)
    kv_valid = jax.random.randint(k5, (t_rows,), 1, maxb * bs + 1,
                                  jnp.int32)
    if n_pads:                          # bucket-padding rows: no KV at all
        kv_valid = kv_valid.at[-n_pads:].set(0)
    return q, kp, vp, tables, kv_valid


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4), (4, 1)])
def test_paged_flash_decode_matches_ref(dtype, atol, hq, hkv):
    q, kp, vp, tables, kv_valid = _paged_case(
        0, 6, hq, hkv, 64, nb=10, bs=8, maxb=3, dtype=dtype)
    got = ops.paged_flash_decode(q, kp, vp, tables, kv_valid)
    want = ref.paged_flash_decode_ref(q, kp, vp, tables, kv_valid)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), atol=atol)


def test_paged_flash_decode_sliding_window_matches_ref():
    q, kp, vp, tables, kv_valid = _paged_case(
        1, 5, 4, 2, 64, nb=12, bs=8, maxb=4, dtype=jnp.float32)
    got = ops.paged_flash_decode(q, kp, vp, tables, kv_valid, window=8)
    want = ref.paged_flash_decode_ref(q, kp, vp, tables, kv_valid,
                                      window=8)
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), atol=3e-5)
    # the window actually bites: full-context answer differs
    full = ref.paged_flash_decode_ref(q, kp, vp, tables, kv_valid)
    assert not np.allclose(np.asarray(want), np.asarray(full))


def test_pad_rows_contribute_exactly_zero():
    """Bucket-padding rows (kv_valid == 0) must emit EXACT zeros — the
    packed layout's 'pads cost zero attention mass' invariant, in both the
    kernel and the oracle."""
    q, kp, vp, tables, kv_valid = _paged_case(
        2, 6, 4, 2, 64, nb=10, bs=8, maxb=3, dtype=jnp.float32, n_pads=3)
    for fn in (ops.paged_flash_decode, ref.paged_flash_decode_ref):
        out = np.asarray(fn(q, kp, vp, tables, kv_valid))
        assert np.all(out[-3:] == 0.0), fn
        assert np.all(np.isfinite(out))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), t_rows=st.integers(1, 7),
       hkv=st.sampled_from([1, 2, 4]), bs=st.sampled_from([4, 8]),
       maxb=st.integers(1, 4),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_property_paged_flash_decode(seed, t_rows, hkv, bs, maxb, dtype):
    q, kp, vp, tables, kv_valid = _paged_case(
        seed, t_rows, 4, hkv, 64, nb=max(6, maxb + 2), bs=bs, maxb=maxb,
        dtype=dtype, n_pads=seed % t_rows if t_rows > 1 else 0)
    got = ops.paged_flash_decode(q, kp, vp, tables, kv_valid)
    want = ref.paged_flash_decode_ref(q, kp, vp, tables, kv_valid)
    atol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), atol=atol)


# ---------------------------------------------------------------------------
# engine parity — THE correctness contract
# ---------------------------------------------------------------------------

def _prompts(sizes, vocab=500, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=s).tolist() for s in sizes]


def test_paged_matches_wave_greedy_bit_identical(setup):
    """Same admitted set -> bit-identical greedy streams: the paged
    engine's packed prefill + block-gather attention reproduces the wave
    engine token for token, while its bucket ladder keeps every
    admission-driven shape change an exec-cache hit (one rebuild per
    bucket, never a re-jit)."""
    cfg, params = setup
    prompts = _prompts([5, 3, 9, 2, 7, 12])
    wave = ServeEngine(params, cfg, single_device_ctx(),
                       ServeConfig(slots=4, cache_len=96))
    for p in prompts:
        wave.submit(p, max_new=6)
    wave.run_until_drained()
    fw = wave.finished()
    wave.close()

    paged = PagedServeEngine(params, cfg, single_device_ctx(),
                             PagedServeConfig(max_requests=4, cache_len=96,
                                              kv_block=16,
                                              max_tokens_in_flight=16,
                                              min_bucket=4))
    for p in prompts:
        paged.submit(p, max_new=6)
    paged.run_until_drained()
    fp = paged.finished()
    rep = paged.serving_report()
    paged.close()

    assert fw == fp
    assert all(len(v) == 6 for v in fp.values())
    # batch-bucket exec-cache warmth: one rebuild per distinct bucket
    bc = rep["batch_bucket_cache"]
    assert bc["rebuilds"] == len(rep["buckets"])
    assert bc["hits"] > 0
    # packed prefill spends no KV on padding and balances its books
    kv = rep["kv_blocks"]
    assert kv["allocs"] == kv["frees"] and kv["in_use"] == 0


def test_preemption_resume_streams_unchanged(setup):
    """A block-starved pool forces preempt-by-eviction; teacher-forced
    re-prefill of prompt+out must resume every victim bit-identically, so
    the starved run's streams equal the uncontended run's."""
    cfg, params = setup
    prompts = _prompts([20, 18, 16, 22], seed=4)

    def run(n_blocks):
        eng = PagedServeEngine(params, cfg, single_device_ctx(),
                               PagedServeConfig(max_requests=4,
                                                cache_len=48, kv_block=8,
                                                n_blocks=n_blocks,
                                                max_tokens_in_flight=16,
                                                min_bucket=4))
        for p in prompts:
            eng.submit(p, max_new=12)
        eng.run_until_drained()
        fin, rep = eng.finished(), eng.serving_report()
        eng.close()
        return fin, rep

    fin_starved, rep_starved = run(n_blocks=9)   # < 4 requests' worth
    fin_ample, rep_ample = run(n_blocks=0)       # auto: no pressure
    assert rep_starved["scheduler"]["preemptions"] > 0
    assert rep_ample["scheduler"]["preemptions"] == 0
    assert fin_starved == fin_ample


def test_wave_coadmission_keeps_short_stream_unchanged(setup):
    """Wave right-alignment regression: a longer prompt co-admitted into
    the wave pads the short one's prefill, and those pad positions must
    carry zero attention mass — the short request's greedy stream cannot
    move."""
    cfg, params = setup
    short = _prompts([4], seed=5)[0]
    long = _prompts([11], seed=6)[0]

    def run(prompts):
        eng = ServeEngine(params, cfg, single_device_ctx(),
                          ServeConfig(slots=2, cache_len=48))
        rids = [eng.submit(p, max_new=6) for p in prompts]
        eng.run_until_drained()
        fin = eng.finished()
        eng.close()
        return [fin[r] for r in rids]

    alone = run([short])[0]
    together = run([short, long])[0]
    assert alone == together


def test_unallocated_pool_blocks_stay_zero(setup):
    """Pad rows and unadmitted capacity write NOTHING: pool blocks the
    allocator never handed out (it hands out ascending ids, so everything
    above peak_in_use is virgin) must still be exactly zero after a full
    serve."""
    cfg, params = setup
    eng = PagedServeEngine(params, cfg, single_device_ctx(),
                           PagedServeConfig(max_requests=2, cache_len=64,
                                            kv_block=8,
                                            max_tokens_in_flight=8,
                                            min_bucket=4))
    for p in _prompts([6, 9], seed=7):
        eng.submit(p, max_new=4)
    eng.run_until_drained()
    peak = eng.kv.report()["peak_in_use"]
    pool = eng.pool
    eng.close()
    assert 0 < peak < eng.pcfg.n_blocks
    for leaf in (pool["k"], pool["v"]):
        assert np.all(np.asarray(leaf[:, peak:]) == 0.0)
        assert np.any(np.asarray(leaf[:, :peak]) != 0.0)


# ---------------------------------------------------------------------------
# StepProgram batch-shape buckets
# ---------------------------------------------------------------------------

def test_step_program_shape_key_buckets():
    """Each shape_key keys its OWN executable: a revisited bucket is a
    cache hit, a new bucket a rebuild — and the report lists the buckets
    seen (the serve launcher's --assert-warm denominator)."""
    ctx = single_device_ctx()
    builds = []

    def builder():
        builds.append(1)
        return jax.jit(lambda x: x + 1.0)

    prog = StepProgram(builder, ctx)
    prog(jnp.zeros(4), shape_key=4)
    prog(jnp.zeros(8), shape_key=8)
    prog(jnp.zeros(4), shape_key=4)     # revisit: hit, no rebuild
    rep = prog.report()
    prog.close()
    assert len(builds) == 2
    assert rep["shape_buckets"] == [4, 8]
    assert rep["executable_cache"]["rebuilds"] == 2
    assert rep["executable_cache"]["hits"] == 1


# ---------------------------------------------------------------------------
# spans, named scopes and per-request stamps (the profiler's view)
# ---------------------------------------------------------------------------

TICK_SPANS = ["serve.plan", "serve.pack", "serve.issue", "serve.await",
              "serve.fetch", "serve.sample", "serve.commit"]


def test_tick_emits_its_phase_spans_back_to_back(setup, tmp_path):
    """Under a profiler session one tick writes the seven ``serve.*``
    spans once each, in order, each starting where the last ended (to
    within a span's own overhead)."""
    import glob

    cfg, params = setup
    eng = PagedServeEngine(params, cfg, single_device_ctx(),
                           PagedServeConfig(max_requests=2, cache_len=32,
                                            kv_block=8,
                                            max_tokens_in_flight=8,
                                            min_bucket=8))
    eng.submit(_prompts([5])[0], max_new=2)
    eng.tick()                                # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.tick()
    eng.close()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for plane in pd.planes if plane.name == "/host:CPU"
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("serve."))
    assert [name for _, _, name in spans] == TICK_SPANS
    for (_, end, _), (start, _, _) in zip(spans, spans[1:]):
        assert 0 <= start - end < 2e6          # ns: no overlap, no gap


def test_scheduler_stamps_admission_stall_and_first_token():
    """Two 12-token prompts under an 8-row budget: the second waits a
    tick for a row (a stall), is preempted when the first outgrows a
    seven-block pool, and keeps the time of its first admission; the
    scheduler's report sums the stamps."""
    import itertools

    from repro.serving.scheduler import ContinuousScheduler, PagedRequest

    ticks = itertools.count()
    sched = ContinuousScheduler(PagedKVCache(7, 4, 8, 2), max_requests=2,
                                max_tokens_in_flight=8,
                                clock=lambda: float(next(ticks)))
    first = PagedRequest(0, list(range(1, 13)), max_new=16)
    second = PagedRequest(1, list(range(1, 13)), max_new=4)
    sched.submit(first)
    sched.submit(second)
    assert sched.report()["prefill"] == {"requests": 0, "p80_ms": None,
                                         "stall_share": None}
    admitted = []
    while sched.has_work():
        plan = sched.plan_tick()
        admitted.append(second.t_admit)
        sched.commit(plan, {row: 7 for row in plan.sample_rows})
    assert second.preemptions == 1
    assert set(admitted) == {second.t_admit}
    assert second.stall_ticks >= 1 and second.prefill_ticks >= 1
    assert first.stall_ticks == 0 and first.prefill_ticks == 2
    for req in (first, second):
        assert req.t_admit <= req.t_first
    waits = [1e3 * (r.t_first - r.t_admit) for r in (first, second)]
    stalls = second.stall_ticks
    rep = sched.report()["prefill"]
    assert rep["requests"] == 2
    assert rep["p80_ms"] == pytest.approx(np.percentile(waits, 80))
    assert rep["stall_share"] == pytest.approx(
        stalls / (stalls + first.prefill_ticks + second.prefill_ticks))


def test_serve_launcher_prints_prefill_stamps(capsys):
    """``launch/serve.py`` reads the scheduler's stamps: the p80 of first
    admission to first token and the share of stalled ticks; and the
    engine's count of the K/V chunks attention ran."""
    from repro.launch.serve import main

    assert main(["--smoke", "--paged", "on", "--requests", "3",
                 "--max-new", "2", "--max-tokens-in-flight", "8",
                 "--max-requests", "2"]) == 0
    out = capsys.readouterr().out.splitlines()
    line = next(ln for ln in out if "admission to first token" in ln)
    assert "over 3 requests" in line and "% of those ticks" in line
    line = next(ln for ln in out if "K/V chunks" in ln)
    assert line.endswith("(100.0%)")     # smoke prompts fit one chunk


def test_paged_step_hlo_names_its_scopes(setup):
    """``paged_step_texts`` lowers the very module the engine runs (the
    device-trace reduction reads it, ``chipbench/scopes.py``), which
    keeps its module name and the named scopes."""
    import re

    from repro.serving.engine import paged_step_texts

    cfg, params = setup
    scfg = PagedServeConfig(max_requests=2, cache_len=32, kv_block=8,
                            max_tokens_in_flight=8, min_bucket=4)
    eng = PagedServeEngine(params, cfg, single_device_ctx(), scfg)
    issued = []
    real_issue = eng._program.issue
    eng._program.issue = lambda *a, **k: (issued.append(a),
                                          real_issue(*a, **k))[1]
    eng.submit(_prompts([6])[0], max_new=1)
    eng.tick()
    eng.close()
    abstract = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            issued[0])
    ran = eng._step_builder().lower(*abstract).compile().as_text()
    texts = paged_step_texts(cfg, single_device_ctx(), scfg, params)
    assert len(texts) == len(scfg.buckets()) == 2

    def program(text):            # instructions and scopes; no call stacks
        lines = [ln.strip() for ln in text.splitlines() if " = " in ln]
        return ([re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in lines],
                re.findall(r'op_name="([^"]*)"', text))
    assert program(texts[scfg.buckets().index(8)]) == program(ran)
    for text in texts:
        assert text.startswith("HloModule jit_paged_step")
        names = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("/embed/", "/layers/", "/qkv_proj/", "/attn/kv_write/",
                      "/attn/kv_gather/", "/attn/attend/", "/o_proj/",
                      "/mlp/", "/head/"):
            assert any(scope in n for n in names), scope


# ---------------------------------------------------------------------------
# streaming paged attention: chunks gathered in the loop, live bound
# ---------------------------------------------------------------------------

def _dense_gather_attention(q, kp, vp, tables, positions, kv_valid):
    """The former reference path: gather every row's whole view (index i
    is position i), then chunked_attention over it."""
    from repro.models.layers import chunked_attention
    nb, bs, hkv, hd = kp.shape
    t, maxb = tables.shape
    src = (tables[:, :, None] * bs + jnp.arange(bs)).reshape(t, maxb * bs)
    kg = kp.reshape(nb * bs, hkv, hd)[src]
    vg = vp.reshape(nb * bs, hkv, hd)[src]
    return chunked_attention(q, kg, vg, causal=True, q_offset=positions,
                             kv_valid=kv_valid)


@pytest.mark.parametrize("bs,maxb,deepest", [
    (16, 96, 300),        # the deepest row ends in the first of 3 chunks
    (16, 96, 900),        # in the middle chunk
    (16, 96, 1536),       # in the last chunk
    (16, 96, 0),          # an all-padding step: no iteration at all
    (16, 40, 640),        # span 640: a partial last chunk
    (24, 50, 1100),       # blocks of 24 do not divide 512: position rows
], ids=["first", "middle", "last", "all_padding", "partial_last",
        "block_24"])
def test_streaming_paged_attention_bit_identical(bs, maxb, deepest):
    """The streaming reference path equals the dense gather plus
    chunked_attention bit for bit (bf16, GQA group 8), wherever the
    step's deepest row ends: the chunks past it, which the loop skips,
    would have added exact zeros."""
    from repro.models.layers import paged_attention

    t, hq, hkv, hd, nb = 8, 16, 2, 64, 120
    k = jax.random.split(jax.random.PRNGKey(bs * maxb + deepest), 5)
    q = jax.random.normal(k[0], (t, 1, hq, hd)).astype(jnp.bfloat16)
    kp = jax.random.normal(k[1], (nb, bs, hkv, hd)).astype(jnp.bfloat16)
    vp = jax.random.normal(k[2], (nb, bs, hkv, hd)).astype(jnp.bfloat16)
    tables = jax.random.randint(k[3], (t, maxb), 0, nb, jnp.int32)
    kv_valid = jax.random.randint(k[4], (t,), 1, max(deepest, 1) + 1,
                                  jnp.int32)
    kv_valid = kv_valid.at[0].set(deepest).at[-1].set(0)    # one pad row
    if deepest == 0:
        kv_valid = jnp.zeros_like(kv_valid)
    positions = jnp.maximum(kv_valid - 1, 0)
    got = jax.jit(paged_attention)(q, kp, vp, tables, positions=positions,
                                   kv_valid=kv_valid)
    want = jax.jit(_dense_gather_attention)(q, kp, vp, tables, positions,
                                            kv_valid)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert jnp.array_equal(got, want)
    assert np.all(np.asarray(got[-1], np.float32) == 0.0)
    if deepest == 0:
        assert np.all(np.asarray(got, np.float32) == 0.0)


def test_paged_step_holds_no_whole_kv_view(setup):
    """At the top bucket the compiled step never builds the rows' whole
    ``[T, max_blocks*block, kv_w, hd]`` K/V view, in any layout (flat,
    cut into chunks, transposed): no array is that large.  It gathers
    512-position chunks inside the loop, under ``attn/kv_gather``, with
    the math under ``attn/attend``."""
    import math
    import re

    from repro.serving.engine import paged_step_texts

    cfg, params = setup
    scfg = PagedServeConfig(max_requests=2, cache_len=1024, kv_block=16,
                            max_tokens_in_flight=8, min_bucket=8)
    text = paged_step_texts(cfg, single_device_ctx(), scfg, params)[-1]
    t, span = scfg.buckets()[-1], 1024
    kv_w, hd = cfg.n_kv_heads, cfg.head_dim_
    shapes = set(re.findall(r"\w\[([\d,]+)\]", text))
    sizes = [math.prod(int(d) for d in shp.split(",")) for shp in shapes]
    assert max(sizes) < t * span * kv_w * hd
    assert f"{t},512,{kv_w},{hd}" in shapes          # one chunk's view
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/attn/while/body/kv_gather/", "/attn/while/body/attend/"):
        assert any(scope in n for n in names), scope


def test_serving_report_counts_attention_chunks(setup):
    """``attn_chunks``: a 700-token prompt prefilled 256 rows a tick next
    to a 40-token one; each step runs the 512-position chunks up to its
    deepest row, out of the two of the 1024-position span."""
    cfg, params = setup
    eng = PagedServeEngine(params, cfg, single_device_ctx(),
                           PagedServeConfig(max_requests=2, cache_len=1024,
                                            kv_block=16,
                                            max_tokens_in_flight=256,
                                            min_bucket=8))
    plans = []
    real_plan = eng.sched.plan_tick
    eng.sched.plan_tick = lambda: (plans.append(real_plan()), plans[-1])[1]
    long, short = _prompts([700, 40], seed=8)
    eng.submit(long, max_new=3)
    eng.submit(short, max_new=2)
    eng.run_until_drained()
    rep = eng.serving_report()
    eng.close()
    deepest = [max(pos for _, pos, _ in p.rows) + 1 for p in plans if p.rows]
    assert deepest == [256, 512, 700, 701, 702]
    assert rep["steps"] == 5
    assert rep["attn_chunks"] == {"run": sum(-(-d // 512) for d in deepest),
                                  "span": 5 * 2}
    assert rep["attn_chunks"]["run"] == 8


# ---------------------------------------------------------------------------
# the layer loop carries the stacked pool: against the per-layer form
# ---------------------------------------------------------------------------

def _per_layer_step(p, pool, tokens, positions, row_req, block_tables,
                    sample_rows, cfg, ctx, pcfg):
    """The former K/V layer loop of ``paged_decode_step``: a scan over
    each layer's pool sliced out of the stack, the updated pools written
    back (the dense prefix of a MoE stack one layer at a time)."""
    from repro.models import layers as L
    from repro.models import moe as M
    from repro.models.transformer import _paged_head, embed_tokens
    from jax import lax

    valid = row_req >= 0
    btab = block_tables[jnp.clip(row_req, 0, block_tables.shape[0] - 1)]
    kv_valid = jnp.where(valid, positions + 1, 0)
    x = embed_tokens(p, tokens[:, None], cfg, ctx)

    def attn(lp, x, pools):                  # one layer's (k, v) pools
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, new = L.paged_attention_block(
            lp["attn"], h, cfg, ctx, positions=positions, kv_valid=kv_valid,
            pools=tuple(a[None] for a in pools), layer=0, block_tables=btab,
            window_override=pcfg.window_override, impl=pcfg.attn_impl)
        return x + h, tuple(a[0] for a in new)

    def mlp(lp, x):
        return x + L.mlp_block(lp["mlp"], L.rms_norm(x, lp["ln2"],
                                                     cfg.norm_eps), ctx)

    def step(x, inp):
        lp, pools = inp[0], inp[1:]
        x, new = attn(lp, x, pools)
        if "mlp" in lp:
            return mlp(lp, x), new
        y, _, counts = M.moe_layer(
            lp["moe"], L.rms_norm(x, lp["ln2"], cfg.norm_eps), cfg, ctx,
            valid=valid)
        return x + y, new + (counts,)

    pools = (pool["k"], pool["v"])
    npre = cfg.moe.n_dense_prefix if "prefix" in p else 0
    for i in range(npre):
        lp = jax.tree.map(lambda a: a[i], p["prefix"])
        x, new = attn(lp, x, tuple(a[i] for a in pools))
        pools = tuple(a.at[i].set(n) for a, n in zip(pools, new))
        x = mlp(lp, x)
    x, ys = lax.scan(step, x, (p["layers"],)
                     + tuple(a[npre:] for a in pools))
    pools = tuple(a.at[npre:].set(n) for a, n in zip(pools, ys))
    logits = _paged_head(p, x, sample_rows, cfg, ctx)
    out = (logits, {"k": pools[0], "v": pools[1]})
    return out + (ys[-1].sum(axis=0),) if cfg.family == "moe" else out


def _three_ticks(block, maxb):
    """Three packed ticks of three requests (16 rows each, padding
    last): prefill chunks, then decode rows beside later prefill chunks.
    Each request holds ``maxb`` pool blocks of its own, scattered."""
    reqs = 3
    perm = np.random.default_rng(11).permutation(reqs * maxb * 2)[
        :reqs * maxb]
    tables = perm.reshape(reqs, maxb).astype(np.int32)
    plans = [  # (request row, first position, rows) per request
        [(0, 0, 7), (1, 0, 5)],
        [(0, 7, 1), (1, 5, 6), (2, 0, 8)],
        [(0, 8, 1), (1, 11, 1), (2, 8, 9)],
    ]
    ticks = []
    for plan in plans:
        tok = np.zeros(16, np.int32)
        pos = np.zeros(16, np.int32)
        row = np.full(16, -1, np.int32)
        sample = np.zeros(reqs, np.int32)
        i = 0
        for r, first, n in plan:
            tok[i:i + n] = np.arange(n) * 7 + 13 * r + first + 1
            pos[i:i + n] = np.arange(first, first + n)
            row[i:i + n] = r
            sample[r] = i + n - 1
            i += n
        ticks.append(tuple(jnp.asarray(a)
                           for a in (tok, pos, row, tables, sample)))
    return ticks


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("arch", ["glm4-9b", "mixtral-8x7b+prefix"])
def test_paged_step_carries_the_stacked_pool_bit_identical(arch, impl):
    """The step whose layer loop carries the whole stacked K/V pool (each
    layer writing and reading its own blocks in place, the pool donated)
    gives the logits, the written pools and the MoE route counts of the
    per-layer form (each layer's pool sliced out and written back) bit
    for bit, over three ticks that mix prefill and decode rows on a pool
    of random stale contents."""
    import dataclasses

    from repro.models.transformer import PagedConfig
    from repro.serving.engine import paged_step_builder

    cfg = get_config(arch.split("+")[0]).reduced(n_layers=3)
    if arch.endswith("+prefix"):
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_dense_prefix=1))
    ctx = single_device_ctx()
    params = init_params(KEY, cfg)
    block, maxb = 4, 5
    pcfg = PagedConfig(block_size=block, n_blocks=3 * maxb * 2,
                       max_blocks_per_req=maxb, attn_impl=impl)
    shape = (cfg.n_layers, pcfg.n_blocks, block, cfg.n_kv_heads,
             cfg.head_dim_)
    kk, kv = jax.random.split(jax.random.PRNGKey(5))
    stale = {"k": jax.random.normal(kk, shape, cfg.dtype),
             "v": jax.random.normal(kv, shape, cfg.dtype)}
    pool = jax.tree.map(jnp.copy, stale)
    want_pool = jax.tree.map(jnp.copy, stale)       # the oracle's own
    step = paged_step_builder(cfg, ctx, pcfg)
    oracle = jax.jit(lambda p, pl, *a: _per_layer_step(p, pl, *a, cfg, ctx,
                                                       pcfg))
    for args in _three_ticks(block, maxb):
        got = step(params, pool, *args)
        want = oracle(params, want_pool, *args)
        assert len(got) == len(want) == (3 if cfg.family == "moe" else 2)
        pool, want_pool = got[1], want[1]
        assert jnp.array_equal(got[0], want[0])
        for name in ("k", "v"):
            assert jnp.array_equal(pool[name], want_pool[name]), name
        if cfg.family == "moe":
            assert jnp.array_equal(got[2], want[2])
            expert_layers = cfg.n_layers - cfg.moe.n_dense_prefix
            assert int(got[2][-1]) == (expert_layers * cfg.moe.top_k
                                       * int(jnp.sum(args[2] >= 0)))
    assert not jnp.array_equal(pool["k"], stale["k"])
