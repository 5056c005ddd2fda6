"""The program's named scopes on device ops (``chipbench/scopes.py``), the
``attn_share.serve`` reader, and idle gaps named by the program's own
phase spans, on modules compiled on the CPU and synthetic traces."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import bench, scopes as S, trace as T
from chipbench.tests import tiny


def _text(fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _scoped(outer: str):
    def f(x, w):
        with jax.named_scope(outer):
            with jax.named_scope("inner"):
                y = jnp.tanh(x @ w)
        with jax.named_scope("tail"):
            return (y * 2.0).sum(-1)
    return f


def _ops(text: str, t0: float):
    """Every instruction of a module's text that has a scope, as trace
    ops (their text without metadata) one second apart from ``t0``."""
    table = S.scope_table(text)
    ops = []
    for ln in text.splitlines():
        ln = ln.strip().removeprefix("ROOT ")
        if not ln.startswith("%") or " = " not in ln:
            continue
        name, opcode = T.parse_instruction(ln)
        if table[name][0]:
            ops.append(T.Op(name, opcode, t0 + len(ops),
                            t0 + len(ops) + 1, ln.split(", metadata")[0]))
    return ops, table


def test_scope_of_keeps_only_named_scopes():
    assert S.scope_of("jit(paged_step)/while/body/closed_call/attn/attend/"
                      "while/body/closed_call/bqhgd,bchd->bhgqc/dot_general"
                      ) == "attn/attend"
    assert S.scope_of("jit(paged_step)/layers/while/body/dynamic_slice"
                      ) == "layers"
    assert S.scope_of("jit(paged_step)/layers/while/body/closed_call/attn/"
                      "kv_write/jit(take_along_axis)/gather"
                      ) == "layers/attn/kv_write"
    assert S.scope_of("jit(paged_step)/while") == ""


def test_ops_take_the_scope_of_their_ticks_program():
    """Two buckets of one program name instructions alike; each tick's ops
    are read against the program text that agrees with most of them."""
    small = _text(_scoped("attn"), (4, 16), (16, 16))
    large = _text(_scoped("attn"), (8, 16), (16, 16))
    ops_s, tab_s = _ops(small, 0.0)
    ops_l, tab_l = _ops(large, 100.0)
    assert ops_s and {o.name for o in ops_s} & {o.name for o in ops_l}
    dev = T.Device(ops_s + ops_l)
    dev.nest()
    trace = T.Trace({0: dev}, [T.Span("bench.tick", -0.5, 50.0),
                               T.Span("bench.tick", 99.5, 150.0)])
    got = S.assign(trace, [large, small])[0]
    want = [tab_s[o.name][0] for o in ops_s] + \
        [tab_l[o.name][0] for o in ops_l]
    assert got == want
    assert {"attn/inner", "tail"} <= set(got)
    rows = dict(S.breakdown(trace, {0: got}, 0.0, 200.0))
    assert sum(rows.values()) == pytest.approx(len(got))
    share = S.share(trace, {0: got}, 0.0, 200.0, "attn")
    assert share == pytest.approx(got.count("attn/inner") / len(got))


def test_an_op_two_programs_scope_differently_is_ambiguous():
    """Two programs alike but for their scope names: an op matching both
    equally well is ``(ambiguous)``; one that neither names is
    ``(unscoped)``, and so is every op where no text is given."""
    a = _text(_scoped("attn"), (4, 16), (16, 16))
    b = _text(_scoped("mlp"), (4, 16), (16, 16))
    ops, table = _ops(a, 0.0)
    op = next(o for o in ops if table[o.name][0] == "attn/inner")
    stray = T.Op("fusion.999", "fusion", 2.0, 3.0,
                 "%fusion.999 = f32[3]{0} fusion(f32[3]{0} %p)")
    dev = T.Device([op, stray])
    dev.nest()
    trace = T.Trace({0: dev}, [T.Span("bench.tick", -1.0, 10.0)])
    assert S.assign(trace, [a, b])[0] == [S.AMBIGUOUS, S.UNSCOPED]
    only_a = S.assign(trace, [a])
    assert only_a[0] == ["attn/inner", S.UNSCOPED]
    # half the time unscoped: the texts do not fit the trace, no share
    assert S.share(trace, only_a, 0.0, 10.0, "attn") is None
    none = S.assign(trace, [])
    assert none[0] == [S.UNSCOPED, S.UNSCOPED]
    assert S.share(trace, none, 0.0, 10.0, "attn") is None


def test_own_time_ignores_rounding_overlaps():
    """An op that starts a few nanoseconds before the one ahead of it ends
    is not inside it (a chip trace has such pairs); a loop's body is
    inside the loop, and the own times sum to the busy time."""
    text = "%{n} = f32[8]{{0}} {op}(f32[8]{{0}} %x)"
    ops = [T.Op(n, op, a, b, text.format(n=n, op=op)) for n, op, a, b in
           [("a", "fusion", 0.0, 1.0 + 6e-9), ("b", "fusion", 1.0, 3.0),
            ("while.1", "while", 3.0, 10.0), ("c", "fusion", 4.0, 6.0)]]
    dev = T.Device(ops)
    assert S.own_times(dev) == pytest.approx([1.0, 2.0, 5.0, 2.0])
    assert sum(S.own_times(dev)) == pytest.approx(T.total(T.busy(dev, 0, 10)))


def test_idle_gap_is_named_by_the_programs_phase():
    """With the program's spans loaded beside the harness's, a gap inside
    ``bench.tick`` goes to the innermost ``serve.*`` span."""
    text = "%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %x)"
    dev = T.Device([T.Op("a", "fusion", 0.0, 2.0, text.format(n="a")),
                    T.Op("b", "fusion", 8.0, 10.0, text.format(n="b"))])
    dev.nest()
    trace = T.Trace({0: dev}, [T.Span("bench.tick", 0.0, 10.0),
                               T.Span("serve.await", 0.5, 2.1),
                               T.Span("serve.fetch", 2.1, 7.0),
                               T.Span("serve.sample", 7.0, 7.5)])
    assert T.name_gap(trace, (2.0, 8.0)) == "serve.fetch"
    assert dict(T.gap_breakdown(trace, 0.0, 10.0)) == {"serve.fetch": 6.0}


@pytest.fixture(scope="module")
def tiny_texts():
    return S.step_texts(tiny.tiny_serve())


def test_step_texts_cover_every_bucket_with_scopes(tiny_texts):
    from repro.serving.engine import PagedServeConfig
    cell = tiny.tiny_serve()
    assert len(tiny_texts) == len(
        PagedServeConfig(**cell.traffic["engine"]).buckets())
    for text in tiny_texts:
        scopes = {sc for sc, _ in S.scope_table(text).values()}
        assert {"layers/attn/kv_gather", "layers/attn/attend", "layers/mlp",
                "head"} <= scopes


def _run(trace, window):
    cell = tiny.tiny_serve()
    return bench.Run(cell, 0, 1.0, {}, 1, trace=trace, trace_window=window)


def test_attn_share_reader(tiny_texts, monkeypatch):
    """The reader's share is the ``attn`` ops' own time over all of it;
    it reads nothing without a trace or from a program that cannot build
    its step apart from an engine (the parent of the scopes)."""
    ops, table = _ops(tiny_texts[0], 0.0)
    dev = T.Device(ops)
    dev.nest()
    trace = T.Trace({0: dev}, [T.Span("bench.tick", -1.0, len(ops) + 1.0)])
    attn = sum("attn" in table[o.name][0].split("/") for o in ops)
    monkeypatch.setattr(S, "step_texts", lambda cell: tiny_texts)
    read = bench.read_metric("attn_share.serve",
                             _run(trace, (0.0, len(ops) + 1.0)))
    assert 0 < attn < len(ops)
    assert read == pytest.approx(100.0 * attn / len(ops))
    assert bench.read_metric("attn_share.serve", _run(None, None)) is None
    monkeypatch.setattr(S, "step_texts", lambda cell: None)
    assert bench.read_metric("attn_share.serve",
                             _run(trace, (0.0, len(ops) + 1.0))) is None
