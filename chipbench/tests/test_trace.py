"""The trace reduction on a recorded trace of one v5e chip: three steps,
each a jitted matmul and a call of the Pallas chunk_accumulate kernel,
then 20 ms of host sleep inside a ``bench.host_wait`` annotation."""

from pathlib import Path

import pytest

from chipbench import trace as T

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def one_chip():
    return T.load(str(DATA / "1chip.xplane.pb"))


def test_instruction_parsing():
    text = ("%all-reduce-start.3 = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) "
            "all-reduce-start(bf16[8,128]{1,0} %p), replica_groups={{0,1}}")
    assert T.parse_instruction(text) == ("all-reduce-start.3",
                                         "all-reduce-start")
    op = T.Op(*T.parse_instruction(text), 0.0, 1.0, text)
    assert op.collective == "all-reduce" and op.kernel is None
    assert op.label == "all-reduce"


def test_devices_ops_and_annotations(one_chip):
    assert sorted(one_chip.devices) == [0]
    dev = one_chip.devices[0]
    names = [s.name for s in one_chip.host]
    assert names.count("bench.step") == 3
    assert names.count("bench.host_wait") == 3
    kernels = {o.kernel for o in dev.ops if o.kernel}
    assert kernels == {"accumulate"}
    assert {o.label for o in dev.ops if o.kernel} == {"pallas:accumulate"}
    assert not any(o.collective for o in dev.ops)


def test_busy_idle_and_gap_names(one_chip):
    lo, hi = one_chip.host[0].start, one_chip.host[-1].end
    dev = one_chip.devices[0]
    busy = T.total(T.busy(dev, lo, hi))
    assert 0 < busy < 1e-3                       # microseconds of work
    idle = T.idle_share(one_chip, lo, hi)
    assert idle == pytest.approx(1 - busy / (hi - lo))
    gaps = dict(T.gap_breakdown(one_chip, lo, hi))
    # the three sleeps dominate the idle time
    assert gaps["bench.host_wait"] > 0.06
    ops = T.op_breakdown(one_chip, lo, hi)
    assert ops[0][0] == "fusion:convolution_reduce_fusion"
    assert sum(v for _, v in ops) == pytest.approx(busy, rel=1e-6)


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4),
                                                       (6, 10)]
    assert T.clip([(0, 5), (6, 9)], 1, 7) == [(1, 5), (6, 7)]


def test_nested_ops_count_once():
    text = "%{n} = f32[8]{{0}} {op}(f32[8]{{0}} %x)"
    ops = [T.Op("while.1", "while", 0.0, 10.0, text.format(n="while.1",
                                                              op="while")),
           T.Op("fusion.1", "fusion", 1.0, 4.0, text.format(n="fusion.1",
                                                            op="fusion")),
           T.Op("all-reduce.1", "all-reduce", 5.0, 9.0,
                text.format(n="all-reduce.1", op="all-reduce")),
           T.Op("copy.1", "copy", 6.0, 7.0, text.format(n="copy.1",
                                                        op="copy"))]
    dev = T.Device(ops)
    dev.nest()
    assert dev.self_s == [3.0, 3.0, 3.0, 1.0]
    trace = T.Trace({0: dev}, [])
    assert dict(T.op_breakdown(trace, 0.0, 10.0)) == {
        "while": 3.0, "fusion:fusion": 3.0, "all-reduce": 3.0, "copy": 1.0}
    assert T.total(T.busy(dev, 0.0, 10.0)) == 10.0
