"""Double-buffered pipeline + monotonic-counter protocol tests (§3.1)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.pipeline import (MonotonicPipe, StageTimes, N_BUFFERS,
                                 optimal_chunk_bytes, pipeline_time_s)


def test_in_order_delivery():
    pipe = MonotonicPipe()
    chunks = [np.full(4, i) for i in range(10)]
    got = []
    i = j = 0
    while j < len(chunks):
        if i < len(chunks) and pipe.try_produce(chunks[i]):
            i += 1
        out = pipe.try_consume()
        if out is not None:
            got.append(out)
            j += 1
    for want, have in zip(chunks, got):
        np.testing.assert_array_equal(want, have)


def test_producer_blocks_when_buffers_full():
    pipe = MonotonicPipe(n_buffers=2)
    assert pipe.try_produce(np.zeros(1))
    assert pipe.try_produce(np.ones(1))
    # both buffers full and unconsumed -> third produce must block
    assert not pipe.try_produce(np.full(1, 2.0))
    assert pipe.try_consume() is not None
    assert pipe.try_produce(np.full(1, 2.0))  # freed by the consume


def test_consumer_blocks_on_empty():
    pipe = MonotonicPipe()
    assert pipe.try_consume() is None


@given(schedule=st.lists(st.booleans(), min_size=1, max_size=200),
       n_buffers=st.integers(1, 4))
@settings(max_examples=50, deadline=None)
def test_property_no_stale_reads_any_interleaving(schedule, n_buffers):
    """For ANY producer/consumer interleaving, every consumed chunk is the
    one produced for that iteration — the §3.1 strict-ordering claim."""
    pipe = MonotonicPipe(n_buffers=n_buffers)
    produced = 0
    consumed = 0
    for do_produce in schedule:
        if do_produce:
            if pipe.try_produce(np.full(2, produced)):
                produced += 1
        else:
            out = pipe.try_consume()
            if out is not None:
                assert out[0] == consumed, "stale or out-of-order read"
                consumed += 1
    # drain
    while consumed < produced:
        out = pipe.try_consume()
        assert out is not None
        assert out[0] == consumed
        consumed += 1


def test_overlap_beats_serial():
    """Double buffering approaches the slower-stage bound (§3.1)."""
    st_ = StageTimes(pd2h_GBps=26.0, h2cd_GBps=26.0, per_chunk_us=5.0)
    total = 256 * 2**20
    t2 = pipeline_time_s(total, 4 * 2**20, st_, n_buffers=2)
    t1 = pipeline_time_s(total, 4 * 2**20, st_, n_buffers=1)
    assert t2 < 0.6 * t1  # ~2x from overlapping the two stages
    # steady state bounded by the slower stage + one bubble
    slow_bound = total / (26.0e9)
    assert t2 >= slow_bound * 0.99


def test_4mb_buffer_choice():
    """§5.1: 'We empirically select a 4MB buffer' — the model's optimum
    matches for large transfers on H800-like stage speeds."""
    st_ = StageTimes(pd2h_GBps=26.0, h2cd_GBps=26.0, per_chunk_us=50.0)
    best = optimal_chunk_bytes(256 * 2**20, st_)
    assert best in (4 * 2**20, 8 * 2**20, 16 * 2**20)
    # and small chunks are measurably worse at high per-chunk overhead
    t_small = pipeline_time_s(256 * 2**20, 1 << 20, st_)
    t_best = pipeline_time_s(256 * 2**20, best, st_)
    assert t_best < t_small
