"""Path primitives + payload partitioning — FlexLink's data plane, in JAX.

Every primitive here runs inside ``shard_map``.  The *routing* of payload
across primitives — which path carries how many chunks of which collective —
lives one level up in ``routing.py``: a quantized ``RoutePlan`` drives a
single generic ``execute`` driver through the PathExecutor registry.  The
four ``flex_*`` collectives are re-exported from there (see the module
``__getattr__`` at the bottom), so ``collectives.flex_all_reduce`` keeps
working while this module stays free of dispatch logic.

The three route classes (DESIGN.md §3):

  primary : the native XLA collective on the target mesh axis — lowers to the
            axis' ICI links exactly like NCCL's NVLink ring.
  staged  : an explicit ``ppermute`` ring on the same axis.  On hardware this
            models the host-staged path: a logically distinct stream of
            point-to-point transfers with its own channels, chunk grain and
            (in the ring-all-reduce) explicit per-step reduce — the hot spot
            the paper's double-buffered pipeline targets.  The rings are
            *chunk-pipelined*: ``substeps > 1`` splits the segment into
            sub-chunks whose per-step transfers are mutually independent, the
            lowered analogue of the §3.1 PD2H/H2CD double buffer (the
            sub-chunk k+1 permute overlaps the sub-chunk k reduce).  In the
            lowered HLO the ring appears as ``collective-permute`` ops, which
            the roofline attributes to the secondary path class.
  ortho   : neighbor-row detour over an *orthogonal* (otherwise idle) mesh
            axis: ppermute the share one hop along the ortho axis, run the
            primary-axis collective on the neighbor row (whose model-axis
            peers hold exactly the guest payload's shards), ppermute back.
            Correct for ANY ortho-axis sharding of the payload, and the two
            hops ride idle ortho links — the TPU analogue of FlexLink's
            "borrow the idle interconnect" move.

Losslessness (the paper's headline property) is enforced by construction —
all routes move exact bytes, no quantization — and verified bit-exactly
against single-path references in ``tests/test_collectives.py``.

Honest-adaptation note (also in DESIGN.md §3): under perfectly uniform SPMD
the ortho detour cannot reduce the *sum* of bytes crossing the primary axis —
that conservation holds on any torus.  What it does do is (a) move bytes onto
links that are idle at that point of the program, letting XLA's async
scheduler overlap the two streams, and (b) win outright when the workload is
non-uniform across rows (MoE hot experts, ragged batches), which is what the
Stage-2 balancer detects at runtime.  The dry-run roofline quantifies (a)
structurally via the per-axis collective-byte breakdown.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

from repro.core.tuner import SHARE_GRID  # noqa: F401  (re-export for callers)
from repro.kernels import ops as _kops

#: payload partition granularity (chunks); shares in grid units are mapped
#: onto this chunk grid.  16 keeps the jit-variant cache small (DESIGN.md §2).
CHUNK_GRID = 16

PATH_PRIMARY = "primary"
PATH_STAGED = "staged"
PATH_ORTHO = "ortho"
PATH_ORDER = (PATH_PRIMARY, PATH_STAGED, PATH_ORTHO)


# ---------------------------------------------------------------------------
# payload partitioning
# ---------------------------------------------------------------------------

def quantize_shares(shares: Mapping[str, int], order: Sequence[str],
                    grid: int = CHUNK_GRID) -> Dict[str, int]:
    """Map SHARE_GRID-unit shares onto the CHUNK_GRID, preserving the total.

    Largest-remainder rounding; paths with a nonzero share keep at least one
    chunk only if rounding leaves room (a <1/grid share legitimately rounds
    to zero — the tuner treats that as path deactivation).
    """
    total = sum(shares.get(p, 0) for p in order)
    if total <= 0:
        raise ValueError("shares must sum to a positive total")
    raw = {p: shares.get(p, 0) * grid / total for p in order}
    out = {p: int(raw[p]) for p in order}
    rem = grid - sum(out.values())
    by_frac = sorted(order, key=lambda p: raw[p] - out[p], reverse=True)
    for p in by_frac[:rem]:
        out[p] += 1
    return out


def _flatten_pad(x: jax.Array, grid: int) -> Tuple[jax.Array, int]:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % grid
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def partition_payload(x: jax.Array, chunk_units: Mapping[str, int],
                      order: Sequence[str],
                      grid: int = CHUNK_GRID) -> Tuple[Dict[str, jax.Array], int]:
    """Split a tensor into per-path flat segments of `units/grid` each."""
    flat, pad = _flatten_pad(x, grid)
    unit = flat.shape[0] // grid
    segs: Dict[str, jax.Array] = {}
    off = 0
    for p in order:
        u = chunk_units.get(p, 0)
        if u > 0:
            segs[p] = lax.dynamic_slice_in_dim(flat, off * unit, u * unit)
        off += u
    return segs, pad


def merge_payload(segs: Mapping[str, jax.Array], order: Sequence[str],
                  pad: int, shape: Tuple[int, ...],
                  dtype) -> jax.Array:
    """Inverse of partition_payload."""
    parts = [segs[p] for p in order if p in segs]
    flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if pad:
        flat = flat[: flat.shape[0] - pad]
    return flat.reshape(shape).astype(dtype)


def partition_columns(x2d: jax.Array, chunk_units: Mapping[str, int],
                      order: Sequence[str],
                      grid: int = CHUNK_GRID,
                      ) -> Tuple[Dict[str, jax.Array], int]:
    """Split a [lead, F] matrix into per-path column groups.

    Used by collectives whose per-rank structure lives on the leading axis
    (reduce_scatter, all_to_all): every path's segment keeps the full leading
    dim, so each sub-collective preserves the rank-chunk layout.
    Returns ({path: [lead, F_p]}, col_pad).
    """
    lead, f = x2d.shape
    pad = (-f) % grid
    if pad:
        x2d = jnp.pad(x2d, ((0, 0), (0, pad)))
    unit = (f + pad) // grid
    segs: Dict[str, jax.Array] = {}
    off = 0
    for p in order:
        u = chunk_units.get(p, 0)
        if u > 0:
            segs[p] = lax.dynamic_slice_in_dim(x2d, off * unit, u * unit,
                                               axis=1)
        off += u
    return segs, pad


def merge_columns(segs: Mapping[str, jax.Array], order: Sequence[str],
                  pad: int) -> jax.Array:
    parts = [segs[p] for p in order if p in segs]
    out = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    if pad:
        out = out[:, : out.shape[1] - pad]
    return out


# ---------------------------------------------------------------------------
# wire-codec composites (DESIGN.md §12)
#
# A compressed hop is encode -> ppermute wire payload -> decode(-accumulate),
# with the fp8 decompress fused into the staged reduce (kernels/codec.py).
# Each composite carries a straight-through custom_vjp: the backward pass
# treats the codec as identity and rides the inverse permutation raw — the
# standard straight-through estimator for quantized collectives, and the same
# shape of VJP ops.accumulate already uses (without it the pallas_calls are
# opaque to AD and differentiated staged rings fail to lower).  Codecs are
# only ever attached by an opt-in --compress plan, so the default data plane
# never touches these.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _codec_permute(x: jax.Array, axis_name: str,
                   perm: Tuple[Tuple[int, int], ...],
                   codec_name: str) -> jax.Array:
    """ppermute ``x`` through the wire codec: encoded values (+ per-chunk
    scales) cross the link; the receiver decodes back to x's shape/dtype."""
    payload = _kops.wire_encode(x, codec_name=codec_name)
    moved = jax.tree.map(
        lambda t: lax.ppermute(t, axis_name, list(perm)), payload)
    vals, scales = moved if isinstance(moved, tuple) else (moved, None)
    return _kops.wire_decode(vals, scales, codec_name=codec_name,
                             shape=x.shape, dtype=x.dtype)


def _codec_permute_fwd(x, axis_name, perm, codec_name):
    return _codec_permute(x, axis_name, perm, codec_name), None


def _codec_permute_bwd(axis_name, perm, codec_name, _res, g):
    inv = [(d, s) for s, d in perm]
    return (lax.ppermute(g, axis_name, inv),)


_codec_permute.defvjp(_codec_permute_fwd, _codec_permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _codec_permute_accumulate(cur: jax.Array, mine: jax.Array,
                              axis_name: str,
                              perm: Tuple[Tuple[int, int], ...],
                              codec_name: str) -> jax.Array:
    """One compressed ring-reduce step: the running partial crosses the link
    encoded and the receiver dequantizes + accumulates its local chunk in a
    single fused kernel (fp32 accumulation, resolve_accumulate's contract)."""
    payload = _kops.wire_encode(cur, codec_name=codec_name)
    moved = jax.tree.map(
        lambda t: lax.ppermute(t, axis_name, list(perm)), payload)
    vals, scales = moved if isinstance(moved, tuple) else (moved, None)
    return _kops.wire_decode_accumulate(vals, scales, mine,
                                        codec_name=codec_name)


def _codec_permute_accumulate_fwd(cur, mine, axis_name, perm, codec_name):
    return _codec_permute_accumulate(cur, mine, axis_name, perm,
                                     codec_name), None


def _codec_permute_accumulate_bwd(axis_name, perm, codec_name, _res, g):
    inv = [(d, s) for s, d in perm]
    # out = permute(cur) + mine, straight-through: cur's cotangent rides the
    # inverse permutation, mine's passes through (the (g, g) of accumulate).
    return lax.ppermute(g, axis_name, inv), g


_codec_permute_accumulate.defvjp(_codec_permute_accumulate_fwd,
                                 _codec_permute_accumulate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _codec_ring_gather(flat: jax.Array, axis_name: str,
                       codec_name: str) -> jax.Array:
    """Compressed ring all-gather of a flat chunk -> [n, m] rows by rank.

    Encode ONCE at the source and forward the wire payload verbatim: every
    rank decodes the same (values, scales) for row j, so the gather stays
    rank-consistent and each element is quantized exactly once regardless
    of hop count.  (Per-hop recompression would give each rank a different
    error for the same row.)
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    payload = _kops.wire_encode(flat, codec_name=codec_name)
    collected = [payload]
    cur = payload
    for _ in range(n - 1):
        cur = jax.tree.map(lambda t: lax.ppermute(t, axis_name, perm), cur)
        collected.append(cur)
    rows = jnp.stack([
        _kops.wire_decode(p[0] if isinstance(p, tuple) else p,
                          p[1] if isinstance(p, tuple) else None,
                          codec_name=codec_name, shape=flat.shape,
                          dtype=flat.dtype)
        for p in collected])               # entry k holds rank (idx - k) % n
    order = (idx - jnp.arange(n)) % n
    return jnp.take(rows, jnp.argsort(order), axis=0)  # entry j = rank j


def _codec_ring_gather_fwd(flat, axis_name, codec_name):
    return _codec_ring_gather(flat, axis_name, codec_name), None


def _codec_ring_gather_bwd(axis_name, codec_name, _res, g):
    # all-gather transpose (the psum_scatter): rank r's contribution shows
    # up in every rank's row r, so its cotangent is the CROSS-RANK sum of
    # row r — psum the full cotangent, then select our own row
    # (straight-through past the codec).  Selecting before the psum would
    # hand every rank sum_k g_k[k] instead of sum_k g_k[r].
    summed = lax.psum(g, axis_name)
    return (jnp.take(summed, lax.axis_index(axis_name), axis=0),)


_codec_ring_gather.defvjp(_codec_ring_gather_fwd, _codec_ring_gather_bwd)


# ---------------------------------------------------------------------------
# staged-path primitives: chunk-pipelined ppermute rings
# ---------------------------------------------------------------------------

def _ring_perm(n: int) -> List[Tuple[int, int]]:
    return [(i, (i + 1) % n) for i in range(n)]


def _split_subchunks(flat: jax.Array, substeps: int
                     ) -> Tuple[List[jax.Array], int, int]:
    """Split a flat payload into `substeps` equal sub-chunks (pad as needed).

    The sub-chunks are the pipeline's in-flight units: their per-ring-step
    transfers carry no data dependence on each other, so the scheduler can
    overlap sub-chunk k+1's permute with sub-chunk k's reduce — the lowered
    form of the §3.1 double buffer.
    """
    m = flat.shape[-1]
    s = max(1, min(int(substeps), max(m, 1)))
    pad = (-m) % s
    if pad:
        widths = [(0, 0)] * (flat.ndim - 1) + [(0, pad)]
        flat = jnp.pad(flat, widths)
    w = flat.shape[-1] // s
    subs = [lax.dynamic_slice_in_dim(flat, j * w, w, axis=flat.ndim - 1)
            for j in range(s)]
    return subs, pad, s


def ring_all_gather(x: jax.Array, axis_name: str, *,
                    substeps: int = 1, codec: str = "") -> jax.Array:
    """All-gather via N-1 ppermute steps; result ordered by rank like
    ``lax.all_gather(x, axis_name, tiled=False)`` (leading axis = rank).

    ``substeps > 1`` chunk-pipelines the ring: the payload is split into
    sub-chunks forwarded independently each step (pure data movement, so the
    result is bit-identical for any substeps).  ``codec`` (DESIGN.md §12)
    encodes each sub-chunk once at its source and forwards the wire payload
    verbatim — rank-consistent, one quantization per element.
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    subs, pad, s = _split_subchunks(x.reshape(-1), substeps)
    if codec:
        rows = jnp.concatenate(
            [_codec_ring_gather(sub, axis_name, codec) for sub in subs],
            axis=1)
        if pad:
            rows = rows[:, :-pad]
        return rows.reshape((n,) + x.shape)
    collected = [[sub] for sub in subs]
    curs = list(subs)
    for _ in range(n - 1):
        # issue every sub-chunk's permute for this ring step up front: the
        # sends are independent and can overlap downstream consumption
        curs = [lax.ppermute(c, axis_name, perm) for c in curs]
        for j in range(s):
            collected[j].append(curs[j])
    rows = jnp.concatenate([jnp.stack(c) for c in collected], axis=1)
    order = (idx - jnp.arange(n)) % n      # entry k holds rank (idx - k) % n
    inv = jnp.argsort(order)
    rows = jnp.take(rows, inv, axis=0)     # entry j holds rank j
    if pad:
        rows = rows[:, :-pad]
    return rows.reshape((n,) + x.shape)


def ring_reduce_scatter(x: jax.Array, axis_name: str,
                        accumulate=None, *, substeps: int = 1,
                        codec: str = "") -> jax.Array:
    """Reduce-scatter via the classic N-1 step ring, chunk-pipelined.

    `x` has leading dim divisible by N; returns this rank's reduced chunk.
    `accumulate(a, b)` is the per-step reduce — ``a + b`` when None; the
    Pallas ``chunk_accumulate`` kernel is injected by the routing layer for
    floating payloads (the paper's reduce-sum hot spot).  ``substeps > 1``
    splits each rank-chunk into sub-chunks whose transfers interleave across
    ring steps (the §3.1 double-buffered pipeline, lowered).  ``codec``
    (DESIGN.md §12) sends each running partial encoded and replaces the
    accumulate with the fused dequantize-accumulate kernel — the local
    chunks still enter at full precision, only in-flight partials are
    quantized.
    """
    if accumulate is None:
        accumulate = lambda a, b: a + b
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    chunk_shape = (x.shape[0] // n,) + x.shape[1:]
    subs, pad, s = _split_subchunks(x.reshape(n, -1), substeps)
    # step s: rank r sends the partial for chunk (r - s - 1) and
    # receives+reduces the partial for chunk (r - s - 2); after N-1 steps
    # rank r owns fully reduced chunk r — matching psum_scatter's layout.
    perm_t = tuple(perm)
    curs = [jnp.take(sub, (idx - 1) % n, axis=0) for sub in subs]
    for step in range(n - 1):
        # double buffer: all sub-chunk sends of this ring step are issued
        # before any reduce, so transfer j+1 overlaps the accumulate of j
        mines = [jnp.take(sub, (idx - step - 2) % n, axis=0) for sub in subs]
        if codec:
            curs = [_codec_permute_accumulate(c, mine, axis_name, perm_t,
                                              codec)
                    for c, mine in zip(curs, mines)]
        else:
            recvd = [lax.ppermute(c, axis_name, perm) for c in curs]
            curs = [accumulate(r, mine) for r, mine in zip(recvd, mines)]
    out = jnp.concatenate(curs) if s > 1 else curs[0]
    if pad:
        out = out[:-pad]
    return out.reshape(chunk_shape)  # fully reduced chunk idx


def ring_all_reduce(x: jax.Array, axis_name: str, accumulate=None, *,
                    substeps: int = 1, codec: str = "") -> jax.Array:
    """All-reduce = ring reduce-scatter + ring all-gather (2(N-1) steps)."""
    n = axis_size(axis_name)
    flat, pad = _flatten_pad(x, n)
    mine = ring_reduce_scatter(flat.reshape(n, -1), axis_name, accumulate,
                               substeps=substeps, codec=codec)
    gathered = ring_all_gather(mine, axis_name, substeps=substeps,
                               codec=codec)            # [n, chunk] by rank
    # rank r contributed chunk r, so rank order == payload order.
    flat_out = gathered.reshape(-1)
    if pad:
        flat_out = flat_out[:-pad]
    return flat_out.reshape(x.shape)


def ring_all_to_all(x: jax.Array, axis_name: str, *,
                    codec: str = "") -> jax.Array:
    """all-to-all via N-1 ppermute rotations (tiled semantics, axis 0).

    Already pipelined by construction: every rotation is independent, so the
    N-1 permutes can all be in flight at once.  ``codec`` compresses each
    rotation's wire transfer; the resident block never hits a link and stays
    exact.
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    chunk = x.shape[0] // n
    blocks = x.reshape((n, chunk) + x.shape[1:])
    # rotation s delivers block dest=(idx+s)%n to rank (idx+s)%n via
    # ppermute with shift s; the piece we receive comes from rank (idx-s).
    received = [jnp.take(blocks, idx % n, axis=0)]        # s=0: own block
    for s in range(1, n):
        send = jnp.take(blocks, (idx + s) % n, axis=0)
        perm = [(i, (i + s) % n) for i in range(n)]
        if codec:
            got = _codec_permute(send, axis_name, tuple(perm), codec)
        else:
            got = lax.ppermute(send, axis_name, perm)      # from rank idx-s
        received.append(got)
    stacked = jnp.stack(received)        # entry s = block from rank (idx-s)
    order = (idx - jnp.arange(n)) % n
    inv = jnp.argsort(order)
    out = jnp.take(stacked, inv, axis=0)  # entry j = block from rank j
    return out.reshape((n * chunk,) + x.shape[1:])


def tree_all_reduce(x: jax.Array, axis_name: str, *,
                    codec: str = "") -> jax.Array:
    """All-reduce via recursive doubling: log2(N) butterfly steps.

    The paper's §6 future work for the 8-GPU AllReduce problem: a ring pays
    2(N-1) sequential steps, which amplifies secondary-path latency; the
    butterfly pays log2(N), trading 1.7x more wire bytes for 4.7x fewer
    latency units at N=8.  Requires power-of-two N.  ``codec`` compresses
    each butterfly exchange (the local operand stays exact).
    """
    n = axis_size(axis_name)
    assert n & (n - 1) == 0, "recursive doubling needs power-of-two ranks"
    k = 0
    while (1 << k) < n:
        perm = [(i, i ^ (1 << k)) for i in range(n)]
        if codec:
            x = _codec_permute_accumulate(x, x, axis_name, tuple(perm),
                                          codec)
        else:
            x = x + lax.ppermute(x, axis_name, perm)
        k += 1
    return x


# ---------------------------------------------------------------------------
# ortho-route primitives
# ---------------------------------------------------------------------------

def ortho_all_gather(x: jax.Array, axis_name: str, ortho_name: str, *,
                     codec: str = "") -> jax.Array:
    """Gather over `axis_name` routing payload via `ortho_name` links.

    Neighbor-row detour: ppermute the share one step along the idle ortho
    axis, run the primary-axis collective THERE (the neighbor row's model-
    axis peers hold exactly the corresponding shards of the guest payload),
    and ppermute the result back.  Correct for ANY sharding across the
    ortho axis — the operands never mix between ortho rows — and the two
    permutes ride otherwise-idle ortho links.  (On a torus the primary-axis
    byte total is conserved — the win is overlap/scheduling, DESIGN.md §3.)
    """
    m = axis_size(ortho_name)
    if m <= 1:
        return lax.all_gather(x, axis_name)
    fwd = [(i, (i + 1) % m) for i in range(m)]
    bwd = [(i, (i - 1) % m) for i in range(m)]
    if codec:
        guest = _codec_permute(x, ortho_name, tuple(fwd), codec)
        gathered = lax.all_gather(guest, axis_name)     # [n, ...]
        return _codec_permute(gathered, ortho_name, tuple(bwd), codec)
    guest = lax.ppermute(x, ortho_name, fwd)
    gathered = lax.all_gather(guest, axis_name)         # [n, ...]
    return lax.ppermute(gathered, ortho_name, bwd)


def ortho_all_reduce(x: jax.Array, axis_name: str, ortho_name: str, *,
                     codec: str = "") -> jax.Array:
    """All-reduce over `axis_name` via the neighbor-row detour (see
    ortho_all_gather): permute -> psum on the neighbor row -> permute back.
    Lossless for any ortho-axis sharding (with ``codec``, the two detour
    hops carry encoded payloads; the psum itself is native)."""
    m = axis_size(ortho_name)
    if m <= 1:
        return lax.psum(x, axis_name)
    fwd = [(i, (i + 1) % m) for i in range(m)]
    bwd = [(i, (i - 1) % m) for i in range(m)]
    if codec:
        guest = _codec_permute(x, ortho_name, tuple(fwd), codec)
        reduced = lax.psum(guest, axis_name)
        return _codec_permute(reduced, ortho_name, tuple(bwd), codec)
    guest = lax.ppermute(x, ortho_name, fwd)
    reduced = lax.psum(guest, axis_name)
    return lax.ppermute(reduced, ortho_name, bwd)


# ---------------------------------------------------------------------------
# flex_* re-exports: the multi-path collectives now live in the RoutePlan
# engine (routing.py); importing them lazily here avoids a module cycle
# (routing builds on the primitives above) while keeping the historical
# ``collectives.flex_all_reduce`` spelling working.
# ---------------------------------------------------------------------------

_ROUTED = ("flex_all_reduce", "flex_all_gather", "flex_reduce_scatter",
           "flex_all_to_all", "RoutePlan", "build_plan", "execute")


def __getattr__(name: str):
    if name in _ROUTED:
        from repro.core import routing
        return getattr(routing, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
