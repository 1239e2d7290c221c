"""Slot-based continuous-batching serving engine.

A fixed pool of ``batch_size`` KV-cache/state *slots* decodes in lockstep;
each slot carries its own cache depth (``cache_index``), so the moment a
request finishes its slot is refilled from the queue mid-flight
(prefill-into-slot) instead of barriering until the whole batch drains.
This is the decode-axis analogue of the paper's processor-utilization
argument for distributed convolutions: never let a fast processor idle on
the slowest one's critical path.

Scheduling contract:
  * admission: a queued request is prefilled alone at its exact prompt
    length (no padding -> exact for attention *and* recurrent archs), then
    its batch-1 cache row is spliced into the freed slot
    (``transformer.insert_cache_slot``) while other slots keep decoding.
    Pure-attention archs round prompt lengths up to ``prefill_bucket``
    (pad tail masked via ``attn_mask`` — still exact, see
    test_masked_cached_prefill_ignores_pad_tail) so ragged traffic compiles
    at most max_len/bucket prefill variants instead of one per length.
  * decode: one pjit'd step for the whole pool with per-slot write offsets
    and positions; a slot only attends to its own prefix (per-row causal
    masking in the dispatched XLA attention op). Free/finished slots ride along
    masked-out: their sampled tokens are discarded and their rows are fully
    overwritten at the next admission.
  * accounting: per-request EOS/stop tokens, ``max_new_tokens``, and the
    cache-capacity budget are tracked per slot; ``out_tokens`` holds ONLY
    tokens that were really generated (the old wave engine zero-padded).

Sampling is stateless: the key for a sampled token is
``fold_in(fold_in(PRNGKey(engine_seed), request_seed), step)``, a pure
function of the engine seed, the request's ``rng_seed`` (default: its
submission index) and how many tokens that request has produced — never of
which other requests share the batch. Greedy rows take an argmax and touch
no randomness. Together with exact-length prefill this makes every
request's output batch-invariant, greedy or sampled.

``WaveEngine`` keeps the old wave-lockstep *scheduling* (admission only
when every slot is free) on top of the same corrected primitives; it exists
as the benchmark baseline for ``benchmarks/serving_bench.py``.

Graceful degradation (``repro.resilience``): transient faults at the host
scheduling sites never poison the lockstep batch. Admission-time faults
retry with bounded backoff and then fail only the one request
(``finish_reason="error"``); a NaN-logit guard in the sample step fails
only the affected rows after idempotent decode retries (a decode step
rewrites the same cache positions with the same values, so re-running it
is safe); an injected pool starvation rides the normal backpressure path;
a ``kv.check()`` integrity fault triggers a full pool rebuild from
host-side request state (prompts + accepted tokens — K/V projections are
position-local, so re-prefilling reproduces the incrementally-written
cache). Per-request ``deadline_s`` adds ``finish_reason="timeout"``. The
active :func:`repro.resilience.faults.active_campaign` is consulted at the
``admit/*``, ``decode/*`` and ``finish/*`` sites.

Observability: ``serve`` opens profiler spans
(``jax.profiler.TraceAnnotation``, about a microsecond each while no trace
runs), so that a device trace says which host step the device waits on:
``serve/admit`` (one per request, ``rid=`` its index) holding
``serve/prefill``, ``serve/insert`` and ``serve/first_token``;
``serve/step`` (one per decode round) holding ``serve/decode``,
``serve/sample``, ``serve/read`` and ``serve/record``; ``serve/pool_check``
and ``serve/rebuild``. ``Engine.stats`` (:class:`ServeStats`) counts the
work of the last ``serve`` call, and each request carries ``t_admit``,
``t_first`` and ``t_done``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.ops import ExecutionContext
from repro.plan import HardwareTarget, default_target
from repro.resilience import errors as flt
from repro.resilience import faults as fj

from . import kv

PyTree = Any

# never-deadlock backstop: consecutive scheduling rounds with queued work,
# no active slot, and no admission before the loop declares itself stalled
_STALL_LIMIT = 10_000


@dataclasses.dataclass
class Request:
    """One generation request.

    ``stop_tokens``: emitting any of these token ids ends the request; the
    stop token is kept as the last element of ``out_tokens``.
    ``rng_seed``: per-request sampling stream id (default: submission index).
    Fix it to make a sampled request reproducible across batch compositions.
    After serving, ``out_tokens`` holds exactly the generated tokens and
    ``finish_reason`` is one of:
      * ``"stop"``        - a stop token was emitted
      * ``"length"``      - ``max_new_tokens`` reached
      * ``"cache_limit"`` - the ``max_len`` cache filled up first
      * ``"error"``       - an unrecoverable per-request fault (persistent
                            NaN logits on this row, admission retries
                            exhausted); other rows keep decoding
      * ``"timeout"``     - ``deadline_s`` elapsed since admission

    ``deadline_s``: optional wall-clock budget, measured from admission;
    an expired request keeps the tokens generated so far.

    ``t_admit``, ``t_first``, ``t_done``: seconds from the start of the
    ``serve`` call to the request's admission, its first token and its
    finish (``t_first`` stays None if no token was generated).
    """

    prompt: np.ndarray  # (len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0  # 0 = greedy
    stop_tokens: Tuple[int, ...] = ()
    rng_seed: Optional[int] = None
    deadline_s: Optional[float] = None
    out_tokens: Optional[np.ndarray] = None
    finish_reason: Optional[str] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


@dataclasses.dataclass
class ServeStats:
    """What one ``Engine.serve`` call did, counted where the work happens.

    Decode: ``steps`` rounds; ``row_steps_active`` and ``row_steps_total``
    sum the occupied slots and the pool size ``B`` over them. For each
    dispatch of the paged decode program, ``kv_read`` adds the KV positions
    its attention kernel streams (each of the ``B`` rows reads its live
    pages, ``ceil((idx + 1) / block_size) * block_size`` positions; free
    rows ride at ``idx`` 0 and read one page) and ``kv_live`` the
    positions the active rows attend to (each row's masked length,
    ``pos + 1``). Prefill, admissions and rebuilds alike: ``prefill_real``
    sums the real tokens, ``prefill_padded`` the bucket lengths dispatched.
    ``admissions`` counts requests taken into a slot, ``backpressure`` the
    rounds in which the pool could not cover the next request,
    ``admit_retries`` and ``numeric_retries`` the prefill and decode
    re-dispatches after a transient fault, and ``failures`` the requests
    that ended early, by cause (``admit``: retries exhausted; ``numeric``:
    persistent non-finite logits; ``timeout``).

    A dropless expert layer (``ModelConfig.moe_dropless``) counts its work,
    summed over its layers, under ``"prefill"`` and ``"decode"``:
    ``moe_routed`` the (token, expert) pairs chosen over all experts,
    ``moe_held`` those on held experts, ``moe_rows`` the rows the grouped
    matmul computes (padding to its row tile included) and ``moe_groups``
    the held experts with rows, summed over calls (each streams its weights
    once). Free decode rows and bucket padding route nowhere. The device's
    counts are read once, when ``serve`` returns."""

    steps: int = 0
    row_steps_active: int = 0
    row_steps_total: int = 0
    kv_read: int = 0
    kv_live: int = 0
    prefill_real: int = 0
    prefill_padded: int = 0
    admissions: int = 0
    backpressure: int = 0
    admit_retries: int = 0
    numeric_retries: int = 0
    failures: Dict[str, int] = dataclasses.field(
        default_factory=collections.Counter)
    moe_routed: Dict[str, int] = dataclasses.field(default_factory=dict)
    moe_held: Dict[str, int] = dataclasses.field(default_factory=dict)
    moe_rows: Dict[str, int] = dataclasses.field(default_factory=dict)
    moe_groups: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Slot:
    """Host-side bookkeeping for one occupied cache slot."""

    request: Request
    budget: int  # min(max_new_tokens, cache capacity left after the prompt)
    generated: List[int] = dataclasses.field(default_factory=list)
    t0: float = 0.0  # admission wall-clock (deadline anchor)


def plan_batch_size(cfg: ModelConfig, max_len: int, target: HardwareTarget,
                    cap: int = 64, hbm_fraction: float = 0.25,
                    block_size: Optional[int] = None) -> int:
    """Slot-pool size from the target's memory model: how many ``max_len``
    cache rows fit in a fraction of HBM (params/activations keep the rest),
    rounded to the MXU sublane multiple so decode GEMMs keep full rows.
    ``block_size`` switches to block-granular footprints (paged engines):
    admission math then matches actual pool occupancy."""
    slot_words = T.cache_footprint_words(cfg, max_len, block_size=block_size)
    b = int((hbm_fraction * target.hbm_words) // max(slot_words, 1.0))
    b = max(1, min(cap, b))
    if b >= target.align_sublane > 1:
        b -= b % target.align_sublane
    return b


@functools.lru_cache(maxsize=None)
def _make_steps(cfg: ModelConfig, max_len: int, ctx: ExecutionContext):
    """Compiled (prefill, insert, decode, sample) steps, shared across every
    engine with the same (cfg, max_len, ctx) so warm jit caches carry
    over between engines (and between the bench's wave/continuous runs).
    ``ctx`` arrives backend-resolved (``ExecutionContext.resolved``) so the
    cache key cannot alias across environment changes."""

    def prefill(params, tokens, attn_mask, last):  # tokens (1, Lp)
        """Lp is the exact prompt length, or a bucket length with the pad
        tail masked out (attention archs); ``last`` indexes the real last
        token's logits. Pad junk written into the cache tail is hidden by
        per-row causal masking until decode overwrites it in place."""
        cache = T.init_cache(cfg, 1, max_len)
        logits, cache, aux = T.forward(params, cfg, tokens=tokens,
                                       cache=cache,
                                       cache_index=jnp.zeros((), jnp.int32),
                                       attn_mask=attn_mask, ctx=ctx)
        logits = jax.lax.dynamic_index_in_dim(logits, last, axis=1,
                                              keepdims=False)
        # a dropless expert layer's counters ride along (ServeStats.moe_*)
        return (logits, cache, aux) if cfg.moe_dropless else (logits, cache)

    def insert(pool, row, slot):
        return T.insert_cache_slot(pool, row, slot)

    def decode(params, cache, token, index):  # token (B, 1), index (B,)
        logits, cache, aux = T.forward(params, cfg, tokens=token, cache=cache,
                                       cache_index=index, decode=True, ctx=ctx)
        if cfg.moe_dropless:
            return logits[:, -1], cache, aux
        return logits[:, -1], cache

    def sample(logits, base_key, seeds, steps, temps):
        """Row i: greedy argmax if temps[i] == 0, else a categorical draw
        keyed by (base_key, seeds[i], steps[i]) — no shared key state, so
        batch composition can never shift anyone's sampling stream.

        Also returns the per-row NaN/Inf flag (the resilience layer's
        numeric guard): the host already syncs on the sampled tokens every
        step, so the flag rides along with zero extra device round-trips.
        Flagged rows sample from zeroed logits; the engine never records
        their tokens."""
        bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
        safe = jnp.where(bad[:, None], 0.0, logits)
        greedy = jnp.argmax(safe, axis=-1)

        def one(seed, step, row, t):
            key = jax.random.fold_in(jax.random.fold_in(base_key, seed), step)
            return jax.random.categorical(
                key, row / jnp.maximum(t, 1e-6), axis=-1)

        sampled = jax.vmap(one)(seeds, steps, safe, temps)
        toks = jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)
        return toks, bad

    return (jax.jit(prefill),
            jax.jit(insert, donate_argnums=(0,)),
            jax.jit(decode, donate_argnums=(1,)),
            jax.jit(sample))


@functools.lru_cache(maxsize=None)
def _make_paged_steps(cfg: ModelConfig, max_len: int, ctx: ExecutionContext,
                      block_size: int, quantized: bool = False):
    """Compiled (insert, decode) for the paged pool. Prefill and sampling are
    shared with ``_make_steps`` — prefill still runs contiguous at batch 1;
    only its landing in the pool and the decode step are paged.

    ``insert`` retraces per distinct block count (<= max_len/block_size
    variants, the same ladder as the bucketed prefills); ``decode`` takes
    the whole (B, max_len/block_size) tables, so it traces once — positions
    and table *contents* are data, never trace constants. The Pallas kernel
    streams each row's live pages alone, whatever the width; the xla
    gathers read every table slot.

    ``quantized`` targets the int8 pool layout: the scatter quantizes each
    (kv_head, position) row symmetrically over hd (matching the write of
    ``ops.attention_decode_quant``) and lands the int8 codes plus the f32
    scales on the pool's kp/ks/vp/vs leaves.

    ``decode`` donates the pool, and the decode op updates it in place
    layer by layer: the step copies nothing pool-sized."""

    def insert(pool, row, blocks, slot=None):
        """row: batch-1 contiguous cache; blocks: (nt,) ids; slot: the row's
        slot, for the per-slot states beside the pool (short convs)."""
        nt = blocks.shape[0]

        def block_rows(r, hp=None):
            """r (R, 1, KV, max_len, hd) -> (R, nt, KV, bs, hp), heads
            zero-padded to the pool's ``hp``."""
            R, _, KV, L, hd = r.shape
            hp = hp or hd
            rb = r[:, 0, :, :min(nt * block_size, L), :]
            # max_len below a whole block, or a lane-padded pool: zero-pad
            rb = jnp.pad(rb, ((0, 0), (0, 0),
                              (0, max(0, nt * block_size - L)), (0, hp - hd)))
            return rb.reshape(R, KV, nt, block_size, hp).transpose(
                0, 2, 1, 3, 4)

        def scatter(p, r):  # p (R, nb, KV, bs, hp)
            return p.at[:, blocks].set(
                block_rows(r, p.shape[-1]).astype(p.dtype))

        def scatter_q(p, s, r):  # + s (R, nb, KV, bs): per-row f32 scales
            rb = block_rows(r).astype(jnp.float32)
            amax = jnp.max(jnp.abs(rb), axis=-1)
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            q = jnp.clip(jnp.round(rb / scale[..., None]), -127.0,
                         127.0).astype(jnp.int8)
            return p.at[:, blocks].set(q), s.at[:, blocks].set(scale)

        out = {}
        for u, leaves in pool.items():
            if "conv" in leaves:
                out[u] = {"conv": leaves["conv"].at[:, slot].set(
                    row[u]["conv"][:, 0].astype(leaves["conv"].dtype))}
            elif quantized:
                kp, ks = scatter_q(leaves["kp"], leaves["ks"], row[u]["k"])
                vp, vs = scatter_q(leaves["vp"], leaves["vs"], row[u]["v"])
                out[u] = {"kp": kp, "ks": ks, "vp": vp, "vs": vs}
            else:
                out[u] = {"kp": scatter(leaves["kp"], row[u]["k"]),
                          "vp": scatter(leaves["vp"], row[u]["v"])}
        return out

    def decode(params, pool, token, index, tables):  # token (B,1), index (B,)
        logits, pool, aux = T.forward(params, cfg, tokens=token, cache=pool,
                                      cache_index=index, decode=True, ctx=ctx,
                                      block_tables=tables)
        if cfg.moe_dropless:
            return logits[:, -1], pool, aux
        return logits[:, -1], pool

    return (jax.jit(insert, donate_argnums=(0,)),
            jax.jit(decode, donate_argnums=(1,)))


class Engine:
    """Continuous-batching engine over a fixed slot pool.

    ``batch_size=None`` sizes the pool from the ``HardwareTarget``'s memory
    model (``plan_batch_size``); ``ctx=None`` builds the execution context
    from ``target`` (backend per the ``repro.ops`` resolution order).

    ``paged`` (default: on for models of attention and short-conv blocks,
    ``transformer.PAGED_KINDS``; a short conv's state lives per slot beside
    the pool) replaces the per-slot contiguous KV assumption with the
    ``repro.serving.kv`` block pool: admission *reserves* a request's whole
    block budget up front (shared prompt-prefix blocks counted once,
    refcounted), turns pool exhaustion into backpressure (the request waits
    in queue) instead of an overcommit,
    and the decode step reads K/V straight out of the pool through per-row
    block tables (``ops.attention_decode`` — Pallas end-to-end, no
    capability fallback). ``num_blocks=None`` sizes the pool for every slot
    to reach ``max_len``, capped by the target's HBM budget
    (``kv.plan_pool_blocks``).

    ``kv_dtype="int8"`` (paged only) quantizes the pool: int8 blocks plus
    per-(block, head, position) f32 scales — (0.25 + 1/hd) words per cached
    element instead of bf16's 0.5, so the same HBM budget holds ~2x the
    blocks (``kv.plan_pool_blocks(quantized=True)``) and each decode step
    streams about half the cache words (the Lq=1 memory-independent term of
    ``core.bounds.mixed_precision_attention_bound``). Output quality against
    the bf16 pool is gated in ``benchmarks/quant_bench.py``."""

    def __init__(self, cfg: ModelConfig, params: PyTree, max_len: int = 512,
                 batch_size: Optional[int] = None,
                 ctx: Optional[ExecutionContext] = None,
                 seed: int = 0, target: Optional[HardwareTarget] = None,
                 prefill_bucket: Optional[int] = None,
                 paged: Optional[bool] = None,
                 block_size: int = kv.DEFAULT_BLOCK_SIZE,
                 num_blocks: Optional[int] = None,
                 kv_dtype: str = "bf16",
                 admission_retries: int = 3,
                 numeric_retries: int = 2,
                 retry_backoff_s: float = 0.001):
        assert cfg.causal, "serving requires a decoder model"
        # resilience policy: transient admission faults retry with
        # exponential backoff; NaN decode steps retry idempotently before
        # failing only the affected rows (module docstring)
        self.admission_retries = admission_retries
        self.numeric_retries = numeric_retries
        self.retry_backoff_s = retry_backoff_s
        self.cfg, self.params = cfg, params
        self.max_len = max_len
        self.target = target or default_target()
        if ctx is None:
            ctx = ExecutionContext(target=self.target)
        self.ctx = ctx.resolved()
        pageable = set(cfg.pattern) <= T.PAGED_KINDS
        if paged is None:
            paged = pageable and not cfg.fused_kv_cache
        elif paged and not pageable:
            raise ValueError(
                f"paged KV serves {sorted(T.PAGED_KINDS)} blocks; recurrent "
                f"blocks carry O(1) state (pattern={cfg.pattern})")
        elif paged and cfg.fused_kv_cache:
            raise ValueError("paged KV uses split k/v pools; "
                             "disable fused_kv_cache")
        self.paged = paged
        self.block_size = block_size
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        if kv_dtype == "int8" and not paged:
            raise ValueError("kv_dtype='int8' requires the paged KV pool "
                             "(the quantized layout lives on pool blocks)")
        self.kv_quant = kv_dtype == "int8"
        if batch_size is None:
            batch_size = plan_batch_size(
                cfg, max_len, self.target,
                block_size=block_size if paged else None)
        self.batch_size = batch_size
        if paged:
            self.pool_head_dim = ops.pool_head_dim(
                "attention_decode_quant" if self.kv_quant
                else "attention_decode", cfg.hd, self.ctx)
            if num_blocks is None:
                num_blocks = kv.plan_pool_blocks(
                    cfg, max_len, batch_size, block_size, target=self.target,
                    quantized=self.kv_quant, head_dim=self.pool_head_dim)
            self.num_blocks = num_blocks
            self._paged_insert, self._paged_decode = _make_paged_steps(
                cfg, max_len, self.ctx, block_size,
                quantized=self.kv_quant)
        if prefill_bucket is None:
            # ragged prompts each jit a prefill per distinct length; rounding
            # lengths up to a bucket bounds that to max_len/bucket traces.
            # Masked padded prefill is exact for attention and short convs
            # (causal; the conv state is read at the true length) — other
            # recurrent state consumes every position, so those archs stay
            # at exact lengths (one trace per distinct length).
            prefill_bucket = 16 if pageable else 1
        elif prefill_bucket > 1 and not pageable:
            raise ValueError(
                "prefill_bucket > 1 requires attention and short-conv "
                "blocks: other recurrent blocks fold pad tokens into their "
                "state")
        self.prefill_bucket = max(1, prefill_bucket)
        (self._prefill, self._insert, self._decode, self._sample) = \
            _make_steps(cfg, max_len, self.ctx)
        self.base_key = jax.random.PRNGKey(seed)
        self.stats = ServeStats()  # of the last serve() call

    # -- scheduling policy ----------------------------------------------------
    def _admission_open(self, slots: List[Optional[_Slot]]) -> bool:
        """Continuous batching: any free slot may be refilled immediately."""
        return True

    # -- serving loop ---------------------------------------------------------
    def serve(self, requests: List[Request]) -> List[Request]:
        B = self.batch_size
        for r in requests:
            if not 1 <= len(r.prompt) <= self.max_len:
                raise ValueError(
                    f"prompt length {len(r.prompt)} outside [1, {self.max_len}]")
            if r.max_new_tokens < 1:
                raise ValueError("max_new_tokens must be >= 1")
            if r.rng_seed is not None and not -2**31 <= r.rng_seed < 2**31:
                raise ValueError("rng_seed must fit in int32")
            if r.deadline_s is not None and r.deadline_s <= 0:
                raise ValueError("deadline_s must be positive")
        t_start = time.perf_counter()
        for r in requests:
            r.t_admit = r.t_first = r.t_done = None
        stats = self.stats = ServeStats()
        camp = fj.active_campaign()
        queue: Deque[Tuple[int, Request]] = collections.deque(
            enumerate(requests))
        bs = self.block_size
        # a dropless expert layer's counters, summed on the device and read
        # when serve ends
        moe_counts: Dict[str, jax.Array] = {}

        def counted(out, kind: str):
            """The step's outputs without the expert counters it returns
            (dropless MoE), which are added up for ``ServeStats.moe_*``."""
            if not self.cfg.moe_dropless:
                return out
            # the first sum adds device zeros like the counters, so every
            # sum is the one add program a single request already traces
            prev = moe_counts.get(kind)
            moe_counts[kind] = out[-1] + (
                jnp.zeros_like(out[-1]) if prev is None else prev)
            return out[:-1]

        def prefill(tokens, mask, last):
            return counted(self._prefill(self.params, jnp.asarray(tokens),
                                         jnp.asarray(mask),
                                         jnp.asarray(last, jnp.int32)),
                           "prefill")

        def paged_insert(cache, row, blocks, s):
            # per-slot states beside the pool need the slot; a pure
            # attention pool does not, and its program takes none
            slot = (jnp.asarray(s, jnp.int32) if "conv" in self.cfg.pattern
                    else None)
            return self._paged_insert(cache, row,
                                      jnp.asarray(blocks, jnp.int32), slot)

        if self.paged:
            cache = T.init_paged_cache(self.cfg, self.num_blocks, bs,
                                       quantized=self.kv_quant, batch=B,
                                       head_dim=self.pool_head_dim)
            alloc = kv.BlockAllocator(self.num_blocks)
            tables = np.zeros((B, -(-self.max_len // bs)), np.int32)
            slot_blocks: List[List[int]] = [[] for _ in range(B)]
            # device-side copy of the tables: they only change at
            # admission/finish, so most decode steps skip the upload
            tables_dev: List[jax.Array] = []
        else:
            cache = T.init_cache(self.cfg, B, self.max_len)
        slots: List[Optional[_Slot]] = [None] * B
        tok = np.zeros(B, np.int32)    # last accepted token per slot
        pos = np.zeros(B, np.int32)    # cache depth: next decode write offset
        seeds = np.zeros(B, np.int32)  # per-slot sampling stream ids
        temps = np.zeros(B, np.float32)

        def finish(s: int, reason: str) -> None:
            """Close slot s with ``reason``, keeping its generated tokens,
            and release its resources (the ``finish/*`` campaign site)."""
            slot = slots[s]
            r = slot.request
            r.out_tokens = np.asarray(slot.generated, np.int32)
            r.finish_reason = reason
            r.t_done = time.perf_counter() - t_start
            slots[s] = None
            tok[s], temps[s] = 0, 0.0  # dead row decodes greedily into void
            if self.paged:
                if camp is not None:
                    inj = camp.draw("finish/pool", kinds=("pool",))
                    if inj is not None:  # repaired at the next check/rebuild
                        camp.corrupt_allocator(alloc, inj)
                for bid in slot_blocks[s]:
                    alloc.free(bid)  # shared prefixes -> refcount decrements
                slot_blocks[s] = []
                tables[s, :] = 0  # dead row reads/writes garbage block 0
                tables_dev.clear()

        def record(s: int, t: int) -> None:
            """Account one generated token for slot s; free it when done."""
            slot = slots[s]
            slot.generated.append(int(t))
            r = slot.request
            if int(t) in r.stop_tokens:
                finish(s, "stop")
            elif len(slot.generated) >= slot.budget:
                finish(s, "length" if slot.budget >= r.max_new_tokens
                       else "cache_limit")
            else:
                tok[s] = int(t)

        def reserve(r: Request, budget: int) -> Optional[List[int]]:
            """Reserve the request's whole block budget (prompt + decode
            growth), sharing registered prompt-prefix blocks. None = the pool
            cannot cover it now -> admission backpressure."""
            plen = len(r.prompt)
            need = -(-(plen + budget - 1) // bs)
            chain = kv.prefix_chain(r.prompt, bs)
            hits: List[Tuple[kv.PrefixKey, int]] = []
            for key in chain:
                bid = alloc.lookup(key)
                if bid is None:
                    break  # chained keys: later blocks cannot match either
                hits.append((key, bid))
            fresh = need - len(hits)
            # an evictable hit leaves the available pool the moment we take a
            # reference, so it cannot also satisfy a fresh allocation
            evictable_hits = sum(1 for _, b in hits if alloc.refcount(b) == 0)
            if alloc.available() - evictable_hits < fresh:
                return None
            blocks = [alloc.ref(b) for _, b in hits]
            for key in chain[len(hits):]:
                b = alloc.alloc()
                alloc.register(b, key)  # a full prompt block: shareable
                blocks.append(b)
            while len(blocks) < need:  # partial tail + decode growth: private
                blocks.append(alloc.alloc())
            return blocks

        def rebuild_pool():
            """Fresh allocator + pool, rebuilt from host-side request state.

            Every live slot re-reserves its block budget against the new
            allocator (prefix sharing intact — reservation order is slot
            order, deterministic) and re-prefills exactly the tokens already
            written to the cache (``prompt + generated[:pos - plen]``). K/V
            projections are position-local and RoPE is applied pre-cache, so
            the rebuilt pool matches the incrementally-written one bit for
            bit over every read-visible position."""
            new_alloc = kv.BlockAllocator(self.num_blocks)
            new_cache = T.init_paged_cache(self.cfg, self.num_blocks, bs,
                                           quantized=self.kv_quant, batch=B,
                                           head_dim=self.pool_head_dim)
            tables_dev.clear()
            for s in range(B):
                slot = slots[s]
                if slot is None:
                    slot_blocks[s] = []
                    tables[s, :] = 0
                    continue
                r = slot.request
                plen = len(r.prompt)
                need = len(slot_blocks[s])
                chain = kv.prefix_chain(r.prompt, bs)
                blocks: List[int] = []
                for key in chain:
                    bid = new_alloc.lookup(key)
                    if bid is not None:
                        blocks.append(new_alloc.ref(bid))
                        continue
                    b = new_alloc.alloc()
                    new_alloc.register(b, key)
                    blocks.append(b)
                while len(blocks) < need:
                    blocks.append(new_alloc.alloc())
                slot_blocks[s] = blocks
                tables[s, :] = 0
                tables[s, :len(blocks)] = blocks
                # the cache holds the prompt plus every already-written
                # accepted token; the pending token (tok[s]) is rewritten by
                # the next decode step as usual
                written = int(pos[s])
                lp = min(self.max_len,
                         -(-written // self.prefill_bucket)
                         * self.prefill_bucket)
                tokens = np.zeros((1, lp), np.int32)
                tokens[0, :plen] = r.prompt
                tokens[0, plen:written] = slot.generated[:written - plen]
                mask = np.zeros((1, lp), bool)
                mask[0, :written] = True
                stats.prefill_real += written
                stats.prefill_padded += lp
                _, row = prefill(tokens, mask, written - 1)
                nt = -(-written // bs)
                new_cache = paged_insert(new_cache, row, blocks[:nt], s)
            return new_alloc, new_cache

        def admit_prefill(plen: int, prompt: np.ndarray):
            """Batch-1 prefill with bounded retry-with-backoff around the
            transient-fault sites (``admit/launch`` raises, ``admit/numeric``
            poisons, plus the always-on finite-logits guard). Returns
            ``(logits, cache_row)``, or None once ``admission_retries``
            retries are exhausted — the caller then fails that one request,
            nobody else."""
            lp = min(self.max_len,
                     -(-plen // self.prefill_bucket) * self.prefill_bucket)
            tokens = np.zeros((1, lp), np.int32)
            tokens[0, :plen] = prompt
            mask = np.zeros((1, lp), bool)
            mask[0, :plen] = True
            for attempt in range(self.admission_retries + 1):
                num_inj = None
                try:
                    with jax.profiler.TraceAnnotation("serve/prefill"):
                        if camp is not None:
                            inj = camp.draw("admit/launch",
                                            kinds=("launch", "dma"),
                                            op="prefill")
                            if inj is not None:
                                raise camp.fault_for(inj, op="prefill",
                                                     backend=self.ctx.backend)
                        stats.prefill_real += plen
                        stats.prefill_padded += lp
                        logits1, row = prefill(tokens, mask, plen - 1)
                        if camp is not None:
                            num_inj = camp.draw("admit/numeric",
                                                kinds=("numeric",),
                                                op="prefill")
                            if num_inj is not None:
                                logits1 = camp.corrupt_output(logits1, num_inj)
                        if not np.all(np.isfinite(
                                np.asarray(logits1, np.float32))):
                            raise flt.NumericFault(
                                "non-finite prefill logits", op="prefill",
                                injection=num_inj)
                except flt.TransientFault as e:
                    last = attempt == self.admission_retries
                    if camp is not None:
                        camp.resolve(e, "row_failed" if last else "retried")
                    if not last:
                        stats.admit_retries += 1
                        time.sleep(min(self.retry_backoff_s * (2 ** attempt),
                                       0.05))
                    continue
                return logits1, row
            return None

        rounds = 0
        stall = 0  # consecutive no-slot no-admission rounds with queued work
        while queue or any(s is not None for s in slots):
            rounds += 1
            # -- deadline sweep: expire requests past their wall budget -----
            now = time.monotonic()
            for s in range(B):
                slot = slots[s]
                if (slot is not None
                        and slot.request.deadline_s is not None
                        and now - slot.t0 >= slot.request.deadline_s):
                    stats.failures["timeout"] += 1
                    finish(s, "timeout")
            # -- pool integrity: check every round under a campaign (finish
            # may have just corrupted the allocator), periodically otherwise;
            # a tripped invariant rebuilds pool + allocator from host state
            if self.paged and (camp is not None or rounds % 256 == 0):
                if camp is not None:
                    inj = camp.draw("decode/pool", kinds=("pool",))
                    if inj is not None:
                        camp.corrupt_allocator(alloc, inj)
                try:
                    with jax.profiler.TraceAnnotation("serve/pool_check"):
                        alloc.check()
                except flt.PoolIntegrityFault:
                    with jax.profiler.TraceAnnotation("serve/rebuild"):
                        alloc, cache = rebuild_pool()
                    if camp is not None:
                        camp.resolve_kind("pool", "rebuilt")
            # -- admission: prefill queued requests into freed slots --------
            admitted = 0
            if queue and self._admission_open(slots):
                for s in range(B):
                    if not queue or slots[s] is not None:
                        continue
                    rid, r = queue[0]
                    plen = len(r.prompt)
                    # token 1 comes from the prefill logits; token k needs a
                    # cache write at plen + k - 2 <= max_len - 1
                    budget = min(r.max_new_tokens, self.max_len - plen + 1)
                    if self.paged:
                        if camp is not None:
                            inj = camp.draw("admit/oom", kinds=("oom",))
                            if inj is not None:
                                # injected pool starvation rides the normal
                                # backpressure path: the request just waits
                                camp.resolve(inj, "backpressure")
                                stats.backpressure += 1
                                break
                        blocks = reserve(r, budget)
                        if blocks is None:
                            if not any(x is not None for x in slots):
                                try:
                                    with jax.profiler.TraceAnnotation(
                                            "serve/pool_check"):
                                        alloc.check()
                                except flt.PoolIntegrityFault:
                                    # a corrupted allocator can fake
                                    # exhaustion: repair, retry next round
                                    with jax.profiler.TraceAnnotation(
                                            "serve/rebuild"):
                                        alloc, cache = rebuild_pool()
                                    if camp is not None:
                                        camp.resolve_kind("pool", "rebuilt")
                                    break
                                raise flt.AdmissionImpossible(
                                    f"paged KV pool of {self.num_blocks} "
                                    f"blocks cannot ever admit a "
                                    f"{plen}-token prompt with budget "
                                    f"{budget}; raise num_blocks",
                                    num_blocks=self.num_blocks,
                                    blocks_needed=-(-(plen + budget - 1)
                                                    // bs),
                                    available_blocks=alloc.available(),
                                    live_blocks=alloc.live_blocks())
                            stats.backpressure += 1
                            break  # backpressure: wait for a slot to finish
                        slot_blocks[s] = blocks
                        tables[s, :] = 0
                        tables[s, :len(blocks)] = blocks
                        tables_dev.clear()
                    with jax.profiler.TraceAnnotation("serve/admit",
                                                      rid=rid):
                        queue.popleft()
                        admitted += 1
                        stats.admissions += 1
                        r.t_admit = time.perf_counter() - t_start
                        out = admit_prefill(plen, r.prompt)
                        if out is None:
                            # transient faults exhausted the retry budget:
                            # this request alone fails; its reservation is
                            # returned
                            r.out_tokens = np.asarray([], np.int32)
                            r.finish_reason = "error"
                            r.t_done = time.perf_counter() - t_start
                            stats.failures["admit"] += 1
                            if self.paged:
                                for bid in slot_blocks[s]:
                                    alloc.free(bid)
                                slot_blocks[s] = []
                                tables[s, :] = 0
                                tables_dev.clear()
                            continue
                        logits1, row = out
                        slots[s] = _Slot(request=r, budget=budget,
                                         t0=time.monotonic())
                        seeds[s] = (r.rng_seed if r.rng_seed is not None
                                    else rid)
                        temps[s] = r.temperature
                        pos[s] = plen
                        with jax.profiler.TraceAnnotation("serve/insert"):
                            if self.paged:
                                # land the prompt's blocks in the pool (a
                                # shared hit is rewritten with bit-identical
                                # K/V: same tokens, positions, params; RoPE
                                # is applied pre-cache)
                                nt = -(-plen // bs)
                                cache = paged_insert(
                                    cache, row, slot_blocks[s][:nt], s)
                            else:
                                cache = self._insert(cache, row, s)
                        with jax.profiler.TraceAnnotation("serve/first_token"):
                            first, _ = self._sample(
                                logits1, self.base_key,
                                jnp.asarray(seeds[s:s + 1]),
                                jnp.zeros(1, jnp.int32),
                                jnp.asarray(temps[s:s + 1]))
                            first = int(np.asarray(first)[0])
                        r.t_first = time.perf_counter() - t_start
                        record(s, first)
            active = [s for s in range(B) if slots[s] is not None]
            if not active:
                # everything admitted this round finished instantly, or
                # admission backpressured with an empty pool. The stall
                # backstop turns a scheduler that stopped making progress
                # into a typed fatal instead of a silent infinite loop.
                stall = 0 if admitted else stall + 1
                if stall > _STALL_LIMIT and queue:
                    raise flt.SchedulerStall(
                        f"no admission progress for {stall} rounds with "
                        f"{len(queue)} request(s) queued and no active slot",
                        queued=len(queue), rounds=rounds)
                continue
            stall = 0
            # -- one lockstep decode step over the pool ---------------------
            # Free rows ride along at a clamped offset; their writes land in
            # rows that are fully overwritten at the next insert (contiguous)
            # or in reserved garbage block 0 (paged) and their samples are
            # never recorded (active-slot masking). A decode step rewrites
            # the same cache positions with the same values, so the numeric
            # retry below can simply re-run it.
            with jax.profiler.TraceAnnotation("serve/step"):
                stats.steps += 1
                stats.row_steps_active += len(active)
                stats.row_steps_total += B
                steps = np.array([len(slots[s].generated) if slots[s] else 0
                                  for s in range(B)], np.int32)
                idx = np.where([slots[s] is not None for s in range(B)],
                               pos, 0)
                for attempt in range(self.numeric_retries + 1):
                    with jax.profiler.TraceAnnotation("serve/decode"):
                        if self.paged:
                            stats.kv_read += sum(
                                -(-(int(i) + 1) // bs) * bs for i in idx)
                            stats.kv_live += sum(int(pos[s]) + 1
                                                 for s in active)
                            if not tables_dev:  # dead rows: all block 0
                                tables_dev.append(jnp.asarray(tables))
                            logits, cache = counted(self._paged_decode(
                                self.params, cache, jnp.asarray(tok)[:, None],
                                jnp.asarray(idx, jnp.int32), tables_dev[0]),
                                "decode")
                        else:
                            logits, cache = counted(self._decode(
                                self.params, cache, jnp.asarray(tok)[:, None],
                                jnp.asarray(idx, jnp.int32)), "decode")
                    num_inj = None
                    if camp is not None:
                        num_inj = camp.draw("decode/numeric",
                                            kinds=("numeric",), op="decode")
                        if num_inj is not None:
                            logits = camp.corrupt_rows(logits, active,
                                                       num_inj)
                    with jax.profiler.TraceAnnotation("serve/sample"):
                        nxt_dev, bad_dev = self._sample(
                            logits, self.base_key, jnp.asarray(seeds),
                            jnp.asarray(steps), jnp.asarray(temps))
                    with jax.profiler.TraceAnnotation("serve/read"):
                        nxt = np.asarray(nxt_dev)
                        bad = np.asarray(bad_dev)
                    bad_rows = [s for s in active if bad[s]]
                    if not bad_rows:
                        if camp is not None and num_inj is not None:
                            camp.resolve(num_inj, "retried")  # unreachable
                        break
                    last = attempt == self.numeric_retries
                    if camp is not None and num_inj is not None:
                        camp.resolve(num_inj,
                                     "row_failed" if last else "retried")
                    if last:
                        # persistent NaN on these rows: fail them alone, keep
                        # their generated-so-far tokens, never record this
                        # step
                        for s in bad_rows:
                            stats.failures["numeric"] += 1
                            finish(s, "error")
                        break
                    stats.numeric_retries += 1
                    time.sleep(min(self.retry_backoff_s * (2 ** attempt),
                                   0.05))
                with jax.profiler.TraceAnnotation("serve/record"):
                    for s in active:
                        if slots[s] is None:
                            continue  # failed/expired rows were closed above
                        pos[s] += 1
                        record(s, int(nxt[s]))
        if camp is not None:
            # the pool dies with the loop: any still-latent corruption from a
            # last-round finish is discarded wholesale — the degenerate
            # rebuild — so the accounting never shows a swallowed fault
            camp.resolve_kind("pool", "rebuilt")
        for kind, tot in moe_counts.items():
            stats.moe_routed[kind], stats.moe_held[kind], \
                stats.moe_rows[kind], stats.moe_groups[kind] = \
                (int(v) for v in np.asarray(tot))
        return requests


class WaveEngine(Engine):
    """Wave-lockstep baseline: the old engine's scheduling (admit a full
    batch, then barrier until every request in it finishes) on top of the
    same corrected slot primitives. Kept as the benchmark baseline so
    ``benchmarks/serving_bench.py`` can show what continuous batching buys
    on mixed prompt/output lengths."""

    def _admission_open(self, slots: List[Optional[_Slot]]) -> bool:
        return all(s is None for s in slots)
