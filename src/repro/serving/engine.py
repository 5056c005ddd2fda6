"""Batched serving engine over the decode step.

Wave-scheduled continuous batching: requests are admitted in waves that
fill the free slots; each wave's prompts are prefilled together through the
decode path (teacher-forced, one fused call per prompt position), then the
engine emits one fused decode step per tick for every active slot.
Finished slots retire independently (EOS or max_new) and free capacity for
the next wave — per-slot positions keep retired/late slots consistent.

Admitted slots get their cache/state rows zeroed (batch axis 1 in every
cache leaf).  Unequal-length prompts in a wave are right-aligned: shorter
prompts see hold tokens first, which attention masks out via kv_valid /
position overwrites; for SSM families this is left-pad semantics (pad
tokens do enter the state — the standard trade-off of batched SSM serving).

The FlexLink RoutePlan engine sits under every decode collective (via the
ctx's communicators): every executed fused step — prefill ticks included —
replays its collectives into the Stage-2 balancer through the engine's
:class:`~repro.runtime.program.StepProgram`.  A share move re-keys the next
fused step onto the plan-keyed executable cache, so an oscillation back to
a previously-compiled plan reuses the jitted callable (exec-cache hit)
while the plan cache records the move as hit+retrace — both stat blocks
surface in ``comm_report``.  The per-program replay recorder keeps this
engine's Stage-2 feedback disjoint from any other program (a training
loop, another engine) sharing the same memoized communicators.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.config import ArchConfig
from repro.models.layers import ATTN_CHUNK
from repro.models.tp import ParallelCtx
from repro.models.transformer import (DecodeConfig, PagedConfig,
                                      decode_step, init_cache,
                                      init_paged_pool, paged_decode_step)
from repro.runtime.program import StepProgram
from repro.serving.paged_kv import PagedKVCache
from repro.serving.scheduler import ContinuousScheduler, PagedRequest


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    temperature: float = 0.0
    out: List[int] = dataclasses.field(default_factory=list)
    _last: int = 0


@dataclasses.dataclass
class ServeConfig:
    slots: int = 4               # max concurrent requests
    cache_len: int = 128
    eos_id: int = -1             # -1: never stops early


class ServeEngine:
    def __init__(self, params, cfg: ArchConfig, ctx: ParallelCtx,
                 scfg: ServeConfig, seed: int = 0):
        self.p = params
        self.cfg = cfg
        self.ctx = ctx
        self.scfg = scfg
        self.dcfg = DecodeConfig(cache_len_local=scfg.cache_len,
                                 seq_shard=None)
        # latent attention has no dense cache: init_cache names the
        # paged engine
        self.cache = init_cache(cfg, ctx, self.dcfg, scfg.slots)
        self.pos = np.zeros(scfg.slots, np.int32)
        self.active: List[Optional[Request]] = [None] * scfg.slots
        self.queue: List[Request] = []
        self.rng = np.random.default_rng(seed)
        self._next_rid = 0
        self._finished: Dict[int, List[int]] = {}
        self._program = StepProgram(self._decode_builder, ctx)
        self._ticks = 0

    def _decode_builder(self):
        """A FRESH jit wrapper per build — jax.jit memoizes per function
        identity, so the StepProgram's rebuilds must not alias traces."""
        return jax.jit(
            lambda p, c, t, pos: decode_step(p, c, t, pos, self.cfg,
                                             self.ctx, self.dcfg))

    def comm_report(self) -> Dict[str, object]:
        """Per-axis FlexLink tuning + plan-cache stats for this engine
        (each axis block includes the active TimingSource kind and the
        per-slot Stage-2 trajectory), plus its StepProgram's
        executable-cache stats and a serving block (DESIGN.md §13)."""
        rep = dict(self.ctx.comm_report())
        rep["executable_cache"] = self._program.cache.report()
        rep["program"] = self._program.report()
        rep["serving"] = {
            "engine": "wave",
            "ticks": self._ticks,
            "slots": self.scfg.slots,
            "active": sum(1 for r in self.active if r is not None),
            "queued": len(self.queue),
            "finished": len(self._finished),
        }
        return rep

    def save_tuning(self, path: Optional[str] = None) -> int:
        """Persist the engine's converged Stage-1 shares to the warm-start
        TuningProfile (control/profile.py)."""
        return self.ctx.save_tuning_profile(path)

    # -- client API -----------------------------------------------------------
    def submit(self, prompt: List[int], max_new: int = 16,
               temperature: float = 0.0) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, list(prompt), max_new, temperature))
        return rid

    def finished(self) -> Dict[int, List[int]]:
        return dict(self._finished)

    # -- internals --------------------------------------------------------------
    def _fused_step(self, tokens: np.ndarray) -> np.ndarray:
        # StepProgram tick via the issue/await lifecycle (DESIGN.md §11):
        # the fused step is issued asynchronously — its decode-path
        # all_gathers are in flight while the host prepares the tick —
        # and await_all barriers it, closes the issue windows its traced
        # ctx.issue scopes opened, and replays this engine's collectives
        # into Stage 2 (prefill ticks included — with long prompts they
        # are most of the collective traffic).  A share move re-keys the
        # next call; no manual re-jit.
        self._program.issue(self.p, self.cache, jnp.asarray(tokens[:, None]),
                            jnp.asarray(self.pos))
        logits, self.cache = self._program.await_all()[-1]
        return np.asarray(logits)

    def _admit_wave(self) -> None:
        """Fill free slots; prefill the admitted prompts together."""
        free = [s for s in range(self.scfg.slots) if self.active[s] is None]
        wave = []
        while free and self.queue:
            slot = free.pop(0)
            req = self.queue.pop(0)
            self.active[slot] = req
            self.pos[slot] = 0
            wave.append((slot, req))
        if not wave:
            return
        # zero the admitted slots' cache/state rows (batch axis 1)
        slot_ids = np.array([s for s, _ in wave])
        mask_shape = [1, self.scfg.slots]
        sel = np.zeros(self.scfg.slots, bool)
        sel[slot_ids] = True
        sel_j = jnp.asarray(sel)

        def zero_rows(a):
            shape = [1] * a.ndim
            shape[1] = self.scfg.slots
            return jnp.where(sel_j.reshape(shape), jnp.zeros_like(a), a)
        self.cache = jax.tree.map(zero_rows, self.cache)
        max_len = max(len(r.prompt) for _, r in wave)
        # teacher-forced prefill: one fused call per prompt position; slots
        # whose prompt is exhausted (or inactive) repeat a hold token at a
        # frozen position; their state advance is rolled back by kv_valid
        # masking (attention) or by never sampling from them (ssm rollback
        # is avoided by right-aligning: shorter prompts start later).
        starts = {s: max_len - len(r.prompt) for s, r in wave}
        for t in range(max_len - 1):            # last token enters at tick
            toks = np.zeros(self.scfg.slots, np.int32)
            for s, r in wave:
                if t >= starts[s]:
                    toks[s] = r.prompt[t - starts[s]]
            self._fused_step(toks)
            for s, r in wave:
                if t >= starts[s]:
                    self.pos[s] += 1
        for s, r in wave:
            r._last = r.prompt[-1]

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        if req.temperature <= 0:
            return int(logits.argmax())
        z = logits / req.temperature
        z = z - z.max()
        p = np.exp(z) / np.exp(z).sum()
        return int(self.rng.choice(len(p), p=p))

    def tick(self) -> int:
        """Admit + one fused decode step for all active slots."""
        if self.ctx.fault_clock is not None:
            # serving's fabric time is the tick counter: flapping rails
            # ride the same hysteresis rule as training steps
            self.ctx.fault_clock.advance(self._ticks)
        self._ticks += 1
        if any(s is None for s in self.active) and self.queue:
            self._admit_wave()
        act = [s for s in range(self.scfg.slots) if self.active[s]]
        if not act:
            return 0
        toks = np.zeros(self.scfg.slots, np.int32)
        for s in act:
            toks[s] = self.active[s]._last
        logits = self._fused_step(toks)
        for s in act:
            self.pos[s] += 1
            req = self.active[s]
            nxt = self._sample(logits[s], req)
            req.out.append(nxt)
            req._last = nxt
            if len(req.out) >= req.max_new or nxt == self.scfg.eos_id:
                self._finished[req.rid] = req.out
                self.active[s] = None
        return len(act)

    def run_until_drained(self, max_ticks: int = 1000) -> None:
        for _ in range(max_ticks):
            if not self.queue and not any(self.active):
                break
            self.tick()

    def close(self) -> None:
        """Retire the engine's StepProgram: drop its replay recorders from
        the (memoized, process-global) communicators and its compiled
        executables.  Call when discarding an engine in a process that
        keeps serving through other engines on the same axes."""
        self._program.close()


# ---------------------------------------------------------------------------
# continuous batching over a paged KV cache (DESIGN.md §13)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PagedServeConfig:
    """Shape/policy knobs of the continuous-batching engine.

    max_requests        : concurrent admitted requests (block-table rows,
                          logits rows) — R
    cache_len           : per-request token cap (prompt + max_new); rounds
                          up to whole blocks for the gather span
    kv_block            : tokens per physical KV block
    n_blocks            : pool blocks per layer; 0 -> auto-size so every
                          request row can hold a full cache_len (no
                          preemption pressure)
    max_tokens_in_flight: packed-row budget per tick — the top batch-shape
                          bucket
    min_bucket          : smallest bucket of the power-of-two ladder
    attn_impl           : "reference" | "kernel" (PagedConfig.attn_impl)
    """
    max_requests: int = 8
    cache_len: int = 128
    kv_block: int = 16
    n_blocks: int = 0
    max_tokens_in_flight: int = 32
    min_bucket: int = 8
    eos_id: int = -1
    attn_impl: str = "reference"

    def paged(self) -> PagedConfig:
        """The pool and step shape this configuration serves with."""
        maxb = -(-self.cache_len // self.kv_block)
        return PagedConfig(block_size=self.kv_block,
                           n_blocks=self.n_blocks or maxb * self.max_requests,
                           max_blocks_per_req=maxb,
                           attn_impl=self.attn_impl)

    def buckets(self) -> List[int]:
        """The power-of-two batch-shape ladder, topped by the exact
        budget."""
        out: List[int] = []
        b = max(1, self.min_bucket)
        while b < self.max_tokens_in_flight:
            out.append(b)
            b *= 2
        return out + [self.max_tokens_in_flight]


def paged_step_builder(cfg: ArchConfig, ctx: ParallelCtx,
                       pcfg: PagedConfig):
    """A FRESH jit wrapper of the packed step (jax.jit memoizes per
    function identity).  The named function gives the compiled module a
    stable name (``jit_paged_step``) in the device trace.  The pool, K/V
    or latent, is donated: the step writes its rows into it in place
    instead of copying the whole pool into a new buffer every step, so
    a caller must use only the pool the step returns."""
    def paged_step(p, pool, toks, pos, rows, tables, sample):
        return paged_decode_step(p, pool, toks, pos, rows, tables, sample,
                                 cfg, ctx, pcfg)
    return jax.jit(paged_step, donate_argnums=(1,))


def paged_step_texts(cfg: ArchConfig, ctx: ParallelCtx,
                     scfg: PagedServeConfig, params) -> List[str]:
    """The compiled HLO text of the packed step that an engine of
    ``scfg`` runs, at each bucket of its ladder.  ``params`` may be
    arrays or ``jax.ShapeDtypeStruct``s; the step's other arguments are
    built here, as ``tick`` packs them."""
    pcfg = scfg.paged()
    pool = jax.eval_shape(functools.partial(init_paged_pool, cfg, ctx, pcfg))
    step = paged_step_builder(cfg, ctx, pcfg)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    reqs = scfg.max_requests
    return [step.lower(params, pool, i32(b), i32(b), i32(b),
                       i32(reqs, pcfg.max_blocks_per_req), i32(reqs))
            .compile().as_text() for b in scfg.buckets()]


class PagedServeEngine:
    """In-flight (continuous) batching: requests are admitted into free
    token budget every tick — not in waves — with K/V in fixed-size pool
    blocks mapped by per-request block tables (serving/paged_kv.py) and
    tick planning by serving/scheduler.py.

    Every tick packs context-phase (prefill-chunk) and generation-phase
    (decode) rows into ONE fused :func:`paged_decode_step`, padded up to a
    power-of-two bucket so admission-driven shape changes re-key onto the
    StepProgram's executable cache (``shape_key``) instead of re-jitting.
    The packed layout replaces the wave engine's right-aligned prompt
    padding: bucket-padding rows cost zero attention FLOP-mass and zero
    KV blocks, and prefill never burns a full wave-width step per prompt
    position.

    Greedy token streams are bit-identical to :class:`ServeEngine` for
    the same admitted set (the correctness contract): the reference path
    gathers each 512-position chunk from the pool and applies
    chunked_attention's own per-chunk update to the operands the wave
    path sees (layers.paged_attention), and preemption/resume re-prefills
    ``prompt + out`` teacher-forced, reproducing the evicted K/V exactly.
    Requires ``ceil(gather_span/512) == ceil(cache_len/512)`` so both
    paths chunk identically — true whenever cache_len is a multiple of
    kv_block, and of everything <= 512 otherwise rounded within the same
    chunk.
    """

    def __init__(self, params, cfg: ArchConfig, ctx: ParallelCtx,
                 scfg: PagedServeConfig, seed: int = 0):
        self.p = params
        self.cfg = cfg
        self.ctx = ctx
        self.scfg = scfg
        self.pcfg = scfg.paged()
        self.pool = init_paged_pool(cfg, ctx, self.pcfg)
        self.kv = PagedKVCache(self.pcfg.n_blocks, scfg.kv_block,
                               self.pcfg.max_blocks_per_req,
                               scfg.max_requests)
        self.sched = ContinuousScheduler(
            self.kv, max_requests=scfg.max_requests,
            max_tokens_in_flight=scfg.max_tokens_in_flight,
            eos_id=scfg.eos_id)
        self.buckets = scfg.buckets()
        self.rng = np.random.default_rng(seed)
        self._next_rid = 0
        self._finished: Dict[int, List[int]] = {}
        # one exec-cache entry per (bucket, plan) pair
        self._program = StepProgram(self._step_builder, ctx,
                                    capacity=4 * len(self.buckets))
        self._ticks = 0
        self._steps = 0
        self._real_rows = 0
        self._padded_rows = 0
        self._peak_rows = 0
        self._bucket_steps: Dict[int, int] = {}
        # K/V chunks the reference attention ran, and those of the span
        self._chunks_run = 0
        self._chunks_span = 0
        # token-expert pairs of real rows on each held expert, then all
        # (the MoE step's third output, summed over steps)
        self._moe_counts = (np.zeros(cfg.moe.n_held + 1, np.int64)
                            if cfg.family == "moe" else None)

    def _step_builder(self):
        """A fresh jit wrapper per build; the shape_key bucket keeps each
        padded-shape variant on its own cache entry, so one wrapper never
        retraces silently."""
        return paged_step_builder(self.cfg, self.ctx, self.pcfg)

    def _bucket(self, n_rows: int) -> int:
        for b in self.buckets:
            if n_rows <= b:
                return b
        return self.buckets[-1]

    # -- client API -----------------------------------------------------------

    def submit(self, prompt: List[int], max_new: int = 16,
               temperature: float = 0.0) -> int:
        if len(prompt) + max_new > self.scfg.cache_len:
            raise ValueError(
                f"prompt+max_new = {len(prompt) + max_new} exceeds "
                f"cache_len {self.scfg.cache_len}")
        rid = self._next_rid
        self._next_rid += 1
        self.sched.submit(PagedRequest(rid, list(prompt), max_new,
                                       temperature))
        return rid

    def finished(self) -> Dict[int, List[int]]:
        return dict(self._finished)

    # -- internals ------------------------------------------------------------

    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        if temperature <= 0:
            return int(logits.argmax())
        z = logits / temperature
        z = z - z.max()
        prob = np.exp(z) / np.exp(z).sum()
        return int(self.rng.choice(len(prob), p=prob))

    def tick(self) -> int:
        """Plan (admit / pack / maybe preempt), run ONE fused packed step,
        sample sequence-frontier rows, retire finished requests.  Returns
        the number of real (non-padding) rows processed.

        Each phase is a profiler span, back to back over the whole body:
        ``serve.plan``, ``serve.pack`` (host arrays and their upload),
        ``serve.issue``, ``serve.await`` (the program's await, which need
        not wait for the device), ``serve.fetch`` (the logits to the host:
        waits for the step to end, then copies), ``serve.sample``,
        ``serve.commit``."""
        with TraceAnnotation("serve.plan"):
            if self.ctx.fault_clock is not None:
                self.ctx.fault_clock.advance(self._ticks)
            self._ticks += 1
            plan = self.sched.plan_tick()
        if not plan.rows:
            return 0
        with TraceAnnotation("serve.pack"):
            t_b = self._bucket(plan.n_rows)
            tokens = np.zeros(t_b, np.int32)
            positions = np.zeros(t_b, np.int32)
            row_req = np.full(t_b, -1, np.int32)
            for i, (row, pos, tok) in enumerate(plan.rows):
                tokens[i] = tok
                positions[i] = pos
                row_req[i] = row
            sample_rows = np.zeros(self.scfg.max_requests, np.int32)
            for row, idx in plan.sample_rows.items():
                sample_rows[row] = idx
            args = [jnp.asarray(a) for a in (tokens, positions, row_req,
                                             self.kv.tables, sample_rows)]
        # issue/await lifecycle (DESIGN.md §11): the packed step's decode
        # collectives are in flight while the host finishes the tick
        with TraceAnnotation("serve.issue"):
            self._program.issue(self.p, self.pool, *args, shape_key=t_b)
        with TraceAnnotation("serve.await"):
            logits, self.pool, *counts = self._program.await_all()[-1]
        with TraceAnnotation("serve.fetch"):
            if counts:
                logits, (counts,) = jax.device_get((logits, counts))
                self._moe_counts += counts
            else:
                logits = np.asarray(logits)
        with TraceAnnotation("serve.sample"):
            sampled = {}
            for row in plan.sample_rows:
                req = self.sched.active[row]
                sampled[row] = self._sample(logits[row], req.temperature)
        with TraceAnnotation("serve.commit"):
            for req in self.sched.commit(plan, sampled):
                self._finished[req.rid] = req.out
            self._steps += 1
            self._real_rows += plan.n_rows
            self._padded_rows += t_b - plan.n_rows
            self._peak_rows = max(self._peak_rows, plan.n_rows)
            self._bucket_steps[t_b] = self._bucket_steps.get(t_b, 0) + 1
            if self.pcfg.attn_impl == "reference":
                # the step stops at the chunk of its deepest row
                # (layers.paged_attention); padding rows sit at position 0
                self._chunks_run += -(-(int(positions.max()) + 1)
                                      // ATTN_CHUNK)
                self._chunks_span += -(-self.pcfg.max_blocks_per_req
                                       * self.pcfg.block_size // ATTN_CHUNK)
        return plan.n_rows

    def run_until_drained(self, max_ticks: int = 10000) -> None:
        for _ in range(max_ticks):
            if not self.sched.has_work():
                break
            self.tick()

    # -- reporting / lifecycle ------------------------------------------------

    def serving_report(self) -> Dict[str, object]:
        """The engine's counts since it was built.  ``attn_chunks``: the
        512-position K/V chunks the reference attention ran (``run``, up
        to each step's deepest row) against those of the whole block-table
        span (``span``), summed over steps; zero under the kernel.
        ``moe`` (MoE family): token-expert pairs routed by real rows
        (``assigned``), those that fell on the experts this device holds
        (``held``), and ``per_expert`` for each held expert, summed over
        the expert layers and steps."""
        ec = self._program.cache.report()
        lookups = ec["hits"] + ec["rebuilds"]
        rep = {
            "engine": "paged",
            "ticks": self._ticks,
            "steps": self._steps,
            "tokens_in_flight": {
                "budget": self.scfg.max_tokens_in_flight,
                "peak": self._peak_rows,
            },
            "rows": {"real": self._real_rows, "padded": self._padded_rows},
            "buckets": {str(b): n
                        for b, n in sorted(self._bucket_steps.items())},
            "batch_bucket_cache": {
                "hits": ec["hits"], "rebuilds": ec["rebuilds"],
                "hit_rate": round(ec["hits"] / lookups, 4)
                if lookups else 0.0,
            },
            "scheduler": self.sched.report(),
            "kv_blocks": self.kv.report(),
            "attn_chunks": {"run": self._chunks_run,
                            "span": self._chunks_span},
        }
        if self._moe_counts is not None:
            per = [int(n) for n in self._moe_counts[:-1]]
            rep["moe"] = {"assigned": int(self._moe_counts[-1]),
                          "held": sum(per), "per_expert": per}
        return rep

    def comm_report(self) -> Dict[str, object]:
        rep = dict(self.ctx.comm_report())
        rep["executable_cache"] = self._program.cache.report()
        rep["program"] = self._program.report()
        rep["serving"] = self.serving_report()
        return rep

    def save_tuning(self, path: Optional[str] = None) -> int:
        return self.ctx.save_tuning_profile(path)

    def close(self) -> None:
        self._program.close()
