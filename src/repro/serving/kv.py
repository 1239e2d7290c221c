"""The serving engine's KV cache behind one store contract (:class:`SlotStore`:
a contiguous row per slot; :class:`PagedStore`: the block pool below), and
the pool's fixed-size blocks, block tables and prefix sharing.

The serving engine's decode cost is almost pure KV-cache traffic (the
memory-independent term of ``core.bounds.attention_bound`` dominates at
Lq = 1), so the pool exists to make that traffic proportional to *live*
tokens rather than to ``batch * max_len``:

- The cache is one physical pool of ``num_blocks`` fixed-size blocks
  (``block_size`` token positions each, vLLM-style); a request holds a
  *block table* — the list of physical block ids backing its logical
  positions — instead of a contiguous slice.
- Full prompt blocks are content-addressed by a chained hash key
  (``parent_key, block_tokens``), so two requests sharing a system prompt
  share physical blocks with reference counting; the pool charges the prefix
  once.
- Allocation is explicit: ``BlockAllocator.alloc`` raises :class:`BlockOOM`
  when the pool (plus the LRU pool of retained rc=0 prefix blocks) is
  exhausted, and the engine turns that into admission backpressure rather
  than silent eviction of live state.
- Block id 0 is a reserved garbage block: dead batch rows and padded table
  entries point at it, so lockstep decode can write/read it harmlessly.

``plan_pool_blocks`` sizes the pool from ``HardwareTarget.hbm_words`` the
same way ``Engine.plan_batch_size`` sizes the slot pool.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.models import transformer as T
from repro.resilience.errors import (AdmissionImpossible, PoolIntegrityFault,
                                     TransientFault)

DEFAULT_BLOCK_SIZE = 16
GARBAGE_BLOCK = 0

# A full prompt block's content address: (parent block's key or None, the
# block_size token ids it holds). Chaining the parent key makes equal token
# windows at different prefix positions distinct, like vLLM's hash chain.
PrefixKey = Tuple[Optional[tuple], Tuple[int, ...]]


class BlockOOM(TransientFault):
    """The pool cannot satisfy an allocation; admission must back off.

    A :class:`repro.resilience.errors.TransientFault` (still a
    ``RuntimeError`` through the taxonomy base): pool pressure is the
    canonical recoverable condition — the engine answers with bounded
    backpressure, never an abort."""


def prefix_chain(tokens: Sequence[int], block_size: int) -> List[PrefixKey]:
    """Content keys for every FULL block of ``tokens``, in chain order.

    Only full blocks are shareable: a partial tail block will be appended to
    during decode, so it is always private to its request."""
    keys: List[PrefixKey] = []
    parent: Optional[PrefixKey] = None
    for s in range(0, len(tokens) - block_size + 1, block_size):
        key: PrefixKey = (parent, tuple(int(t) for t in tokens[s:s + block_size]))
        keys.append(key)
        parent = key
    return keys


class BlockAllocator:
    """Refcounted block allocator with LRU retention of shareable blocks.

    States a (non-reserved) block can be in — exactly one at any time:

    - **free**: on the free list, contents meaningless.
    - **in use**: refcount >= 1 (held by one or more requests).
    - **evictable**: refcount == 0 but registered under a prefix key; its
      contents are kept so a future request with the same prefix can revive
      it. Evicted (moved to free) lazily, oldest first, only when the free
      list runs dry.

    ``num_blocks`` counts the whole pool including reserved ids, matching the
    physical pool array's leading axis.
    """

    def __init__(self, num_blocks: int,
                 reserved: Sequence[int] = (GARBAGE_BLOCK,)):
        if num_blocks <= len(reserved):
            raise ValueError(
                f"pool of {num_blocks} blocks leaves nothing to allocate "
                f"after {len(reserved)} reserved")
        self.num_blocks = num_blocks
        self.reserved = tuple(reserved)
        self._free: collections.deque[int] = collections.deque(
            b for b in range(num_blocks) if b not in self.reserved)
        self._rc: Dict[int, int] = {}
        self._key_of: Dict[int, PrefixKey] = {}
        self._block_of: Dict[PrefixKey, int] = {}
        # rc==0 registered blocks, insertion order == LRU order
        self._evictable: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict())

    # -- allocation ---------------------------------------------------------

    def alloc(self) -> int:
        """A block with refcount 1. Raises :class:`BlockOOM` when neither the
        free list nor the evictable LRU can supply one."""
        if self._free:
            bid = self._free.popleft()
        elif self._evictable:
            bid, _ = self._evictable.popitem(last=False)  # oldest first
            del self._block_of[self._key_of.pop(bid)]
        else:
            raise BlockOOM(
                f"all {self.num_blocks - len(self.reserved)} allocatable "
                f"blocks are referenced")
        self._rc[bid] = 1
        return bid

    def ref(self, bid: int) -> int:
        """Take an additional reference (reviving an evictable block)."""
        rc = self._rc.get(bid, 0)
        if rc == 0:
            if bid not in self._evictable:
                raise ValueError(f"block {bid} is not live or evictable")
            del self._evictable[bid]
        self._rc[bid] = rc + 1
        return bid

    def free(self, bid: int) -> None:
        """Drop one reference. A registered block that reaches refcount 0
        becomes evictable (contents retained for prefix reuse); an anonymous
        one returns to the free list."""
        rc = self._rc.get(bid, 0)
        if rc <= 0:
            raise ValueError(f"double free of block {bid}")
        if rc > 1:
            self._rc[bid] = rc - 1
            return
        del self._rc[bid]
        if bid in self._key_of:
            self._evictable[bid] = None  # most-recently-used end
        else:
            self._free.append(bid)

    # -- prefix sharing -----------------------------------------------------

    def lookup(self, key: PrefixKey) -> Optional[int]:
        return self._block_of.get(key)

    def register(self, bid: int, key: PrefixKey) -> None:
        """Content-address a live block so later requests can share it."""
        if self._rc.get(bid, 0) <= 0:
            raise ValueError(f"cannot register non-live block {bid}")
        other = self._block_of.get(key)
        if other is not None and other != bid:
            if self._rc.get(other, 0) > 0:
                raise ValueError(f"key already registered to live block "
                                 f"{other}")
            # left evictable when an earlier block of its chain was evicted,
            # so no lookup can reach it: the new block supersedes it
            del self._evictable[other], self._key_of[other]
            self._free.append(other)
        prev = self._key_of.get(bid)
        if prev is not None and prev != key:
            del self._block_of[prev]
        self._key_of[bid] = key
        self._block_of[key] = bid

    # -- accounting ---------------------------------------------------------

    def refcount(self, bid: int) -> int:
        return self._rc.get(bid, 0)

    def available(self) -> int:
        """Blocks an alloc() can obtain right now (free + evictable)."""
        return len(self._free) + len(self._evictable)

    def live_blocks(self) -> int:
        """Blocks with refcount >= 1."""
        return len(self._rc)

    def used_words(self, words_per_block: float) -> float:
        """Pool occupancy in words — shared prefix blocks counted ONCE."""
        return self.live_blocks() * words_per_block

    def check(self) -> None:
        """Invariant check: every non-reserved block is in exactly one of
        {free, live, evictable}, and key maps are mutually inverse. Raises
        :class:`PoolIntegrityFault` (transient: the engine rebuilds the
        pool from host-side request state) with occupancy diagnostics."""
        free = set(self._free)
        live = set(self._rc)
        evict = set(self._evictable)
        problems: List[str] = []
        if (free & live) or (free & evict) or (live & evict):
            problems.append("a block is in two states at once")
        if free | live | evict != (
                set(range(self.num_blocks)) - set(self.reserved)):
            problems.append("free|live|evictable does not partition the pool")
        if not all(rc > 0 for rc in self._rc.values()):
            problems.append("non-positive refcount on a live block")
        if {k: b for b, k in self._key_of.items()} != self._block_of:
            problems.append("prefix-key maps are not mutually inverse")
        if not all(b in self._rc or b in self._evictable
                   for b in self._key_of):
            problems.append("a keyed block is neither live nor evictable")
        if problems:
            raise PoolIntegrityFault(
                "; ".join(problems), num_blocks=self.num_blocks,
                free=len(free), live=len(live), evictable=len(evict))


# ---------------------------------------------------------------------------
# Pool sizing (words per block, blocks per HBM budget)
# ---------------------------------------------------------------------------

def block_words(cfg, block_size: int, dtype_itemsize: int = 2,
                quantized: bool = False, head_dim: Optional[int] = None
                ) -> float:
    """32-bit words one physical block occupies across all attention layers
    (K and V, un-repeated GQA heads). ``quantized`` switches to the int8
    pool layout: one byte per element plus one f32 scale per (kv_head,
    position) row — (1 + 4/hd) bytes per element, vs bf16's 2 — so a
    quantized pool packs ~2x the blocks into the same budget (the
    ``capacity_gain`` gate in benchmarks/quant_bench.py). ``head_dim``: the
    pool's (``ops.pool_head_dim``), default the model's."""
    hd = head_dim or cfg.hd
    n_attn = cfg.repeats * sum(1 for kind in cfg.pattern if kind == "attn")
    elems = n_attn * 2 * cfg.n_kv_heads * block_size * hd
    if quantized:
        return elems * (1.0 + 4.0 / hd) / 4.0
    return elems * dtype_itemsize / 4.0


def plan_pool_blocks(cfg, max_len: int, batch_size: int,
                     block_size: int = DEFAULT_BLOCK_SIZE,
                     target=None, hbm_fraction: float = 0.25,
                     dtype_itemsize: int = 2, quantized: bool = False,
                     head_dim: Optional[int] = None) -> int:
    """Pool size in blocks: enough for every slot to hold ``max_len`` tokens
    (plus the reserved garbage block), clamped to ``hbm_fraction`` of the
    target's HBM — but never below one full sequence, mirroring
    ``Engine.plan_batch_size``'s budget policy. ``quantized`` prices blocks
    at the int8+scales layout (see :func:`block_words`)."""
    per_seq = math.ceil(max_len / block_size)
    want = 1 + batch_size * per_seq
    if target is None:
        return want
    budget = hbm_fraction * target.hbm_words
    cap = 1 + int(budget // max(
        block_words(cfg, block_size, dtype_itemsize, quantized=quantized,
                    head_dim=head_dim),
        1.0))
    return max(min(want, cap), 1 + per_seq)


# ---------------------------------------------------------------------------
# The engine's stores: one contract, a contiguous and a paged implementation
# ---------------------------------------------------------------------------

_slot_insert = jax.jit(T.insert_cache_slot, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _decode_step(cfg, ctx):
    """The compiled decode step over a contiguous cache, or a paged pool
    through its whole (B, max_len / block_size) ``tables``: the Pallas
    kernel streams each row's live pages alone, the xla gathers read every
    table slot. Positions and table contents are data, so it traces once a
    layout. It donates the cache, which the paged op updates in place (no
    pool-sized copy). Shared by every store with the same (cfg, ctx)."""

    def decode(params, cache, token, index, tables=None):  # token (B, 1)
        logits, cache, aux = T.forward(params, cfg, tokens=token, cache=cache,
                                       cache_index=index, decode=True, ctx=ctx,
                                       block_tables=tables)
        if cfg.moe_dropless:
            return logits[:, -1], cache, aux
        return logits[:, -1], cache

    return jax.jit(decode, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _paged_insert(block_size: int, quantized: bool):
    """The compiled landing of a batch-1 contiguous prefill row in the
    pool's blocks. It retraces per distinct block count (<= max_len /
    block_size variants, the same ladder as the bucketed prefills).

    ``quantized`` targets the int8 pool layout: the scatter quantizes each
    (kv_head, position) row symmetrically over hd (matching the write of
    ``ops.attention_decode_quant``) and lands the int8 codes plus the f32
    scales on the pool's kp/ks/vp/vs leaves."""

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

    return jax.jit(insert, donate_argnums=(0,))


class SlotStore:
    """One contiguous ``max_len`` cache row per slot: the store for every
    block kind, and the paged store's reference in tests. The contract, one
    ``Engine.serve`` call between ``open`` and ``close``: ``reserve`` a
    slot's cache for a request (False: backpressure), ``release`` it,
    ``insert`` a prefilled batch-1 row, ``decode`` one step (the program's
    outputs but the cache), ``check`` the bookkeeping (False: repaired),
    ``inject`` a campaign's fault at a site. Here a row is always there and
    the next insert overwrites it whole, so those last four do nothing."""

    def __init__(self, cfg, max_len: int, batch: int, ctx):
        self.cfg, self.max_len, self.batch = cfg, max_len, batch
        self.decode_step = _decode_step(cfg, ctx)

    def open(self, stats, camp, refill) -> None:
        self.cache = T.init_cache(self.cfg, self.batch, self.max_len)

    def close(self) -> None:
        self.cache = None

    def reserve(self, slot: int, request, budget: int) -> bool:
        return True

    def release(self, slot: int) -> None:
        pass

    def inject(self, site: str) -> None:
        pass

    def check(self) -> bool:
        return True

    def insert(self, row, slot: int, length: int) -> None:
        self.cache = _slot_insert(self.cache, row, slot)

    def decode(self, params, tok, idx, active) -> tuple:
        return self._step(params, tok, idx)

    def _step(self, params, tok, idx, *tables) -> tuple:
        out = self.decode_step(params, self.cache, jnp.asarray(tok)[:, None],
                               jnp.asarray(idx, jnp.int32), *tables)
        self.cache = out[1]
        return out[:1] + out[2:]


class PagedStore(SlotStore):
    """The block pool with its :class:`BlockAllocator` and per-slot block
    tables (a short conv's state rides per slot beside it), under the
    contract of :class:`SlotStore`. A reservation is the request's whole
    block budget, shared prompt-prefix blocks counted once. Heads the Pallas
    kernel cannot page are lane-padded (``ops.pool_head_dim``);
    ``num_blocks=None`` plans the pool (:func:`plan_pool_blocks`)."""

    def __init__(self, cfg, max_len: int, batch: int, ctx,
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 num_blocks: Optional[int] = None, target=None,
                 quantized: bool = False):
        super().__init__(cfg, max_len, batch, ctx)
        self.block_size, self.quantized = block_size, quantized
        self.head_dim = ops.pool_head_dim(
            "attention_decode_quant" if quantized else "attention_decode",
            cfg.hd, ctx)
        if num_blocks is None:
            num_blocks = plan_pool_blocks(
                cfg, max_len, batch, block_size, target=target,
                quantized=quantized, head_dim=self.head_dim)
        self.num_blocks = num_blocks
        self.insert_step = _paged_insert(block_size, quantized)

    def open(self, stats, camp, refill) -> None:
        """A fresh pool for one ``serve`` call, counting into ``stats``,
        drawing ``camp``'s pool faults. ``refill(slot)`` gives a live slot's
        prompt, the batch-1 cache row of all it has written, and its length."""
        self.stats, self.camp, self.refill = stats, camp, refill
        self._fresh()

    def _fresh(self) -> None:
        self.cache = None  # the old pool goes before the new one is made
        self.cache = T.init_paged_cache(
            self.cfg, self.num_blocks, self.block_size,
            quantized=self.quantized, batch=self.batch,
            head_dim=self.head_dim)
        self.alloc = BlockAllocator(self.num_blocks)
        self.tables = np.zeros(
            (self.batch, -(-self.max_len // self.block_size)), np.int32)
        self.blocks: List[List[int]] = [[] for _ in range(self.batch)]
        # the tables on the device: they change only at admission and
        # finish, so most decode steps skip the upload
        self.tables_dev = None

    def _take(self, prompt, need: int) -> Optional[List[int]]:
        """``need`` blocks for a request, its registered prompt-prefix
        blocks shared, its other full prompt blocks registered for later
        requests; None when the pool cannot cover them now."""
        alloc = self.alloc
        chain = prefix_chain(prompt, self.block_size)
        hits: List[int] = []
        for key in chain:
            bid = alloc.lookup(key)
            if bid is None:
                break  # chained keys: later blocks cannot match either
            hits.append(bid)
        # an evictable hit leaves the available pool the moment we take a
        # reference, so it cannot also satisfy a fresh allocation
        evictable_hits = sum(1 for b in hits if alloc.refcount(b) == 0)
        if alloc.available() - evictable_hits < need - len(hits):
            return None
        blocks = [alloc.ref(b) for b in hits]
        for key in chain[len(hits):]:
            b = alloc.alloc()
            alloc.register(b, key)  # a full prompt block: shareable
            blocks.append(b)
        while len(blocks) < need:  # partial tail + decode growth: private
            blocks.append(alloc.alloc())
        return blocks

    def _hold(self, slot: int, blocks: List[int]) -> None:
        self.blocks[slot] = blocks
        self.tables[slot, :] = 0
        self.tables[slot, :len(blocks)] = blocks
        self.tables_dev = None

    def reserve(self, slot: int, request, budget: int) -> bool:
        plen = len(request.prompt)
        need = -(-(plen + budget - 1) // self.block_size)
        if self.camp is not None:
            inj = self.camp.draw("admit/oom", kinds=("oom",))
            if inj is not None:
                # injected pool starvation rides the normal backpressure
                # path: the request just waits
                self.camp.resolve(inj, "backpressure")
                self.stats.backpressure += 1
                return False
        blocks = self._take(request.prompt, need)
        if blocks is not None:
            self._hold(slot, blocks)
            return True
        if not any(self.blocks):
            # no slot holds a block, so none will be freed: unless a
            # corrupted allocator faked the exhaustion (repaired now; the
            # request is retried next round), it can never be admitted
            if not self.check():
                return False
            raise AdmissionImpossible(
                f"paged KV pool of {self.num_blocks} blocks cannot ever "
                f"admit a {plen}-token prompt with budget {budget}; raise "
                f"num_blocks", num_blocks=self.num_blocks,
                blocks_needed=need, available_blocks=self.alloc.available(),
                live_blocks=self.alloc.live_blocks())
        self.stats.backpressure += 1
        return False

    def release(self, slot: int) -> None:
        for bid in self.blocks[slot]:
            self.alloc.free(bid)  # shared prefixes -> refcount decrements
        self._hold(slot, [])  # a dead row reads/writes garbage block 0

    def inject(self, site: str) -> None:
        if self.camp is not None:
            inj = self.camp.draw(site, kinds=("pool",))
            if inj is not None:  # repaired by the next check's rebuild
                self.camp.corrupt_allocator(self.alloc, inj)

    def check(self) -> bool:
        try:
            with jax.profiler.TraceAnnotation("serve/pool_check"):
                self.alloc.check()
            return True
        except PoolIntegrityFault:
            with jax.profiler.TraceAnnotation("serve/rebuild"):
                self._rebuild()
            if self.camp is not None:
                self.camp.resolve_kind("pool", "rebuilt")
            return False

    def _rebuild(self) -> None:
        """A fresh allocator and pool. Every live slot, in slot order,
        re-reserves its block budget through admission's reservation
        (prefix sharing intact, so the fresh pool fits them) and re-inserts
        everything it has written. K/V projections are position-local and
        RoPE is applied pre-cache, so the rebuilt pool matches the
        incrementally written one bit for bit over every read-visible
        position."""
        live = [(s, len(b)) for s, b in enumerate(self.blocks) if b]
        self._fresh()
        for s, need in live:
            prompt, row, length = self.refill(s)
            self._hold(s, self._take(prompt, need))
            self.insert(row, s, length)

    def insert(self, row, slot: int, length: int) -> None:
        """Land ``row``'s first ``length`` positions in ``slot``'s blocks (a
        shared hit is rewritten with bit-identical K/V)."""
        nt = -(-length // self.block_size)
        # per-slot states beside the pool need the slot; a pure attention
        # pool does not, and its program takes none
        s = jnp.asarray(slot, jnp.int32) if "conv" in self.cfg.pattern \
            else None
        self.cache = self.insert_step(
            self.cache, row, jnp.asarray(self.blocks[slot][:nt], jnp.int32),
            s)

    def decode(self, params, tok, idx, active) -> tuple:
        """One step; ``kv_read`` adds the positions the kernel streams
        (each row's live pages), ``kv_live`` those the active rows attend
        to."""
        bs = self.block_size
        self.stats.kv_read += sum(-(-(int(i) + 1) // bs) * bs for i in idx)
        self.stats.kv_live += sum(int(idx[s]) + 1 for s in active)
        if self.tables_dev is None:  # dead rows: all block 0
            self.tables_dev = jnp.asarray(self.tables)
        return self._step(params, tok, idx, self.tables_dev)
