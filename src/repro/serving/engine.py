"""Slot-based continuous-batching serving engine.

A fixed pool of ``batch_size`` KV-cache/state *slots* decodes in lockstep;
each slot carries its own cache depth (``cache_index``), so the moment a
request finishes its slot is refilled from the queue mid-flight
(prefill-into-slot) instead of barriering until the whole batch drains.
This is the decode-axis analogue of the paper's processor-utilization
argument for distributed convolutions: never let a fast processor idle on
the slowest one's critical path. The KV cache sits behind one store
(``serving.kv``, paged or contiguous), chosen once per engine.

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
    tokens that were really generated.

Sampling is stateless: the key for a sampled token is
``fold_in(fold_in(PRNGKey(engine_seed), request_seed), step)``, a pure
function of the engine seed, the request's ``rng_seed`` (default: its
submission index) and how many tokens that request has produced — never of
which other requests share the batch. Greedy rows take an argmax and touch
no randomness. Together with exact-length prefill this makes every
request's output batch-invariant, greedy or sampled.

Graceful degradation (``repro.resilience``): transient faults at the host
scheduling sites never poison the lockstep batch. Admission-time faults
retry with bounded backoff and then fail only the one request
(``finish_reason="error"``); a NaN-logit guard in the sample step fails
only the affected rows after idempotent decode retries (a decode step
rewrites the same cache positions with the same values, so re-running it
is safe); an injected pool starvation rides the normal backpressure path;
a failed store ``check()`` triggers a full pool rebuild from
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
    """Compiled (prefill, sample) steps, shared across every engine with the
    same (cfg, max_len, ctx) so warm jit caches carry over between engines.
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

    return jax.jit(prefill), jax.jit(sample)


class Engine:
    """Continuous-batching engine over a fixed slot pool.

    ``batch_size=None`` sizes the pool from the ``HardwareTarget``'s memory
    model (``plan_batch_size``); ``ctx=None`` builds the execution context
    from ``target`` (backend per the ``repro.ops`` resolution order).

    ``paged`` (default: on for models of attention and short-conv blocks,
    ``transformer.PAGED_KINDS``) holds the KV cache in ``kv.PagedStore``'s
    block pool, not ``kv.SlotStore``'s contiguous rows: admission
    *reserves* a request's whole block budget up front (shared prompt-prefix
    blocks counted once, refcounted), pool exhaustion is backpressure (the
    request waits in queue), and the decode step reads K/V straight out of
    the pool through per-row block tables (``ops.attention_decode``).
    ``num_blocks=None`` sizes the pool for every slot to reach ``max_len``,
    capped by the target's HBM budget (``kv.plan_pool_blocks``).

    ``kv_dtype="int8"`` (paged only) quantizes the pool: int8 blocks plus
    per-(block, head, position) f32 scales — (0.25 + 1/hd) words per cached
    element instead of bf16's 0.5, so the same HBM budget holds ~2x the
    blocks and each decode step streams about half the cache words. Output
    quality against the bf16 pool is gated in ``benchmarks/quant_bench.py``."""

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
        if batch_size is None:
            batch_size = plan_batch_size(
                cfg, max_len, self.target,
                block_size=block_size if paged else None)
        self.batch_size = batch_size
        if paged:
            self.store = kv.PagedStore(
                cfg, max_len, batch_size, self.ctx, block_size, num_blocks,
                target=self.target, quantized=kv_dtype == "int8")
            self.num_blocks = self.store.num_blocks
        else:
            self.store = kv.SlotStore(cfg, max_len, batch_size, self.ctx)
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
        self._prefill, self._sample = _make_steps(cfg, max_len, self.ctx)
        self.base_key = jax.random.PRNGKey(seed)
        self.stats = ServeStats()  # of the last serve() call

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
        store = self.store
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

        def prefill(seq):
            """Batch-1 prefill of the tokens ``seq`` at their bucket length,
            the pad tail masked: (the last token's logits, the cache row)."""
            n = len(seq)
            lp = min(self.max_len,
                     -(-n // self.prefill_bucket) * self.prefill_bucket)
            tokens = np.zeros((1, lp), np.int32)
            tokens[0, :n] = seq
            mask = np.zeros((1, lp), bool)
            mask[0, :n] = True
            stats.prefill_real += n
            stats.prefill_padded += lp
            return counted(self._prefill(self.params, jnp.asarray(tokens),
                                         jnp.asarray(mask),
                                         jnp.asarray(n - 1, jnp.int32)),
                           "prefill")

        slots: List[Optional[_Slot]] = [None] * B
        tok = np.zeros(B, np.int32)    # last accepted token per slot
        pos = np.zeros(B, np.int32)    # cache depth: next decode write offset
        seeds = np.zeros(B, np.int32)  # per-slot sampling stream ids
        temps = np.zeros(B, np.float32)

        def refill(s: int):
            """Slot s's prompt, and its cache row as written so far — the
            prompt and every accepted token but the pending one (``tok[s]``,
            which the next decode step writes) — with its length."""
            prompt, written = slots[s].request.prompt, int(pos[s])
            seq = list(prompt) + slots[s].generated[:written - len(prompt)]
            return prompt, prefill(seq)[1], written

        def finish(s: int, reason: str) -> None:
            """Close slot s with ``reason``, keeping its generated tokens,
            and release its cache (the ``finish/*`` campaign site)."""
            slot = slots[s]
            r = slot.request
            r.out_tokens = np.asarray(slot.generated, np.int32)
            r.finish_reason = reason
            r.t_done = time.perf_counter() - t_start
            slots[s] = None
            tok[s], temps[s] = 0, 0.0  # dead row decodes greedily into void
            store.inject("finish/pool")  # repaired at the next check/rebuild
            store.release(s)

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

        def admit_prefill(prompt: np.ndarray):
            """Batch-1 prefill with bounded retry-with-backoff around the
            transient-fault sites (``admit/launch`` raises, ``admit/numeric``
            poisons, plus the always-on finite-logits guard). Returns
            ``(logits, cache_row)``, or None once ``admission_retries``
            retries are exhausted — the caller then fails that one request,
            nobody else."""
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
                        logits1, row = prefill(prompt)
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

        store.open(stats, camp, refill)
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
            # -- cache integrity: check every round under a campaign (finish
            # may have just corrupted the allocator), periodically otherwise;
            # a tripped invariant rebuilds the cache from host state
            if camp is not None or rounds % 256 == 0:
                store.inject("decode/pool")
                store.check()
            # -- admission: prefill queued requests into freed slots --------
            admitted = 0
            for s in (range(B) if queue else ()):
                if not queue or slots[s] is not None:
                    continue
                rid, r = queue[0]
                plen = len(r.prompt)
                # token 1 comes from the prefill logits; token k needs a
                # cache write at plen + k - 2 <= max_len - 1
                budget = min(r.max_new_tokens, self.max_len - plen + 1)
                if not store.reserve(s, r, budget):
                    break  # backpressure: wait for a slot to finish
                with jax.profiler.TraceAnnotation("serve/admit", rid=rid):
                    queue.popleft()
                    admitted += 1
                    stats.admissions += 1
                    r.t_admit = time.perf_counter() - t_start
                    out = admit_prefill(r.prompt)
                    if out is None:
                        # transient faults exhausted the retry budget:
                        # this request alone fails; its reservation is
                        # returned
                        r.out_tokens = np.asarray([], np.int32)
                        r.finish_reason = "error"
                        r.t_done = time.perf_counter() - t_start
                        stats.failures["admit"] += 1
                        store.release(s)
                        continue
                    logits1, row = out
                    slots[s] = _Slot(request=r, budget=budget,
                                     t0=time.monotonic())
                    seeds[s] = (r.rng_seed if r.rng_seed is not None
                                else rid)
                    temps[s] = r.temperature
                    pos[s] = plen
                    with jax.profiler.TraceAnnotation("serve/insert"):
                        store.insert(row, s, plen)
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
                        (logits,) = counted(store.decode(
                            self.params, tok, idx, active), "decode")
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
        store.close()
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
