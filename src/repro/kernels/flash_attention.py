"""Blocked (flash-style) attention Pallas kernel with LP-informed tile sizes.

Attention's two GEMMs (QK^T and PV) are 7NL degenerates; the paper's capacity
argument picks the (block_q, block_k) pair: three f32 VMEM residents
(q tile, o tile, running stats) plus streamed k/v tiles must fit M/2.
block_q = block_k = 512 keeps the working set
  (2*bq*dh*4 + 2*bk*dh*2 + bq*bk*4) < 2 MiB  for dh <= 256,
far under VMEM while saturating the MXU (both >= 128).

Causal masking is done per-tile with absolute positions; GQA is handled by
the wrapper (kv heads are gathered, never materialized repeated in HBM).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_model import round_up
from repro.core.tiling import attention_block_size
from repro.plan import HardwareTarget, default_target, pallas_interpret

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def attention_blocks(dh: int, target: HardwareTarget,
                     kv_word: Optional[float] = None) -> tuple[int, int]:
    """(block_q, block_k) from the target's capacity argument (module
    docstring): f32 q/acc/stats residents + streamed k/v tiles must fit the
    double-buffered budget. Delegates to ``core.tiling.attention_block_size``
    — the same closed form the planner's attention plans use — so kernel
    launch geometry and planned tiles can never drift apart. ``kv_word`` is
    the stream width of the actual k/v arrays (words of 32 bits); defaults to
    the target's policy."""
    m_eff = target.memory_model().M_eff
    p_kv = target.precision.p_I if kv_word is None else kv_word
    b = attention_block_size(dh, m_eff, p_kv=p_kv)
    return b, b


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_k: int, q_offset: int, kv_len: int,
                  q_seq_len: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)  # (bk, dh)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if kv_len % block_k != 0:  # padded keys: mask them out unconditionally
        s = jnp.where(kpos < kv_len, s, NEG_INF)
    if causal:
        qidx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if q_seq_len is not None:
            # GQA group folding: row j of the flattened query axis is query
            # j % q_seq_len of its group, so positions wrap per group.
            qidx = qidx % q_seq_len
        s = jnp.where(kpos <= qidx + q_offset, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _store():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _flash_kernel_dyn(offs_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                      m_ref, l_ref, acc_ref, *,
                      scale: float, causal: bool, block_q: int, block_k: int,
                      n_k: int, q_seq_len: Optional[int]):
    """The dynamic twin of ``_flash_kernel``: per-row q_offset and kv_len
    arrive as scalar-prefetch refs (one int32 per BH row) instead of static
    ints, so one trace serves every (offset, length) combination — the decode
    hot path retraces on shape only, never on position."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)  # (bk, dh)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # the length mask is unconditional: it covers both block padding and
    # per-row cache lengths shorter than the padded key axis
    s = jnp.where(kpos < lens_ref[b], s, NEG_INF)
    if causal:
        qidx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if q_seq_len is not None:
            qidx = qidx % q_seq_len  # GQA fold: positions wrap per group
        s = jnp.where(kpos <= qidx + offs_ref[b], s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _store():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (BH, Lq, Dh)  - batch*heads flattened by the wrapper
    k: jax.Array,  # (BH, Lk, Dh)
    v: jax.Array,  # (BH, Lk, Dh)
    causal: bool = True,
    q_offset=0,  # int, or int32 array: scalar or per-row (BH,)
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    target: Optional[HardwareTarget] = None,
    q_seq_len: Optional[int] = None,
    kv_lens: Optional[jax.Array] = None,  # int32 (BH,): valid keys per row
) -> jax.Array:
    """``q_seq_len``: set when the query axis folds GQA groups — q rows are g
    groups of ``q_seq_len`` queries stacked, each group restarting at absolute
    position ``q_offset`` (the repeat-free GQA path; K/V stay un-repeated at
    (B*Hkv, Lk, Dh)). None = plain contiguous positions.

    A traced/array ``q_offset`` or a ``kv_lens`` array selects the dynamic
    kernel: offsets and cache lengths ride as scalar-prefetch operands, so the
    serving engine's lockstep decode (every row at a different position)
    compiles once per shape instead of once per step."""
    BH, Lq, Dh = q.shape
    Lk = k.shape[1]
    if block_q is None or block_k is None:
        if target is not None:
            kv_word = jnp.dtype(k.dtype).itemsize / 4.0
            tq, tk = attention_blocks(Dh, target, kv_word=kv_word)
        else:
            tq, tk = DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
        block_q = block_q if block_q is not None else tq
        block_k = block_k if block_k is not None else tk
    params = pltpu.CompilerParams(vmem_limit_bytes=(
        target if target is not None else default_target()).vmem_limit_bytes)
    scale = 1.0 / (Dh ** 0.5)
    bq = min(block_q, round_up(Lq, 8))
    bk = min(block_k, round_up(Lk, 8))
    Lqp, Lkp = round_up(Lq, bq), round_up(Lk, bk)
    if Lqp != Lq:
        q = jnp.pad(q, ((0, 0), (0, Lqp - Lq), (0, 0)))
    if Lkp != Lk:
        # padded keys are masked out via kpos > qpos + Lk guard below
        k = jnp.pad(k, ((0, 0), (0, Lkp - Lk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Lkp - Lk), (0, 0)))
    n_q, n_k = Lqp // bq, Lkp // bk

    if q_seq_len is not None and q_seq_len >= Lq:
        q_seq_len = None  # a single group degenerates to plain positions

    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, Dh), jnp.float32),
    ]
    dynamic = kv_lens is not None or not isinstance(q_offset, int)
    if dynamic:
        offs = jnp.broadcast_to(
            jnp.asarray(q_offset, jnp.int32).reshape(-1), (BH,))
        lens = (jnp.full((BH,), Lk, jnp.int32) if kv_lens is None
                else jnp.broadcast_to(
                    jnp.asarray(kv_lens, jnp.int32).reshape(-1), (BH,)))
        kernel = functools.partial(
            _flash_kernel_dyn, scale=scale, causal=causal,
            block_q=bq, block_k=bk, n_k=n_k, q_seq_len=q_seq_len,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(BH, n_q, n_k),
                in_specs=[
                    pl.BlockSpec((1, bq, Dh), lambda b, i, j, o, s: (b, i, 0)),
                    pl.BlockSpec((1, bk, Dh), lambda b, i, j, o, s: (b, j, 0)),
                    pl.BlockSpec((1, bk, Dh), lambda b, i, j, o, s: (b, j, 0)),
                ],
                out_specs=pl.BlockSpec(
                    (1, bq, Dh), lambda b, i, j, o, s: (b, i, 0)),
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((BH, Lqp, Dh), q.dtype),
            compiler_params=params,
            interpret=pallas_interpret(),
            name="flash_attention",
        )(offs, lens, q, k, v)
        return out[:, :Lq]

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, n_k=n_k, q_offset=q_offset, kv_len=Lk,
        q_seq_len=q_seq_len,
    )
    out = pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Lqp, Dh), q.dtype),
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=pallas_interpret(),
        name="flash_attention",
    )(q, k, v)
    return out[:, :Lq]


# ---------------------------------------------------------------------------
# Paged decode: block-table-gathering attention over the serving KV pool.
# ---------------------------------------------------------------------------

def _paged_decode_kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, *, scale: float):
    """One batch row, all its kv heads: the row's ``n = ceil(len / bs)`` live
    pages are DMA'd by hand in table order into a two-slot VMEM buffer (page
    i + 1 lands while page i folds) and each (kv head, page) folds into that
    head's online softmax. Table slots past ``n`` are never read, so dead
    slots pointing at reserved block 0 move nothing; a row of length 0 moves
    nothing and stores zeros. Positions past the length inside the last live
    page are masked."""
    _, n_kv, g, hd = q_ref.shape
    block_size, w = k_buf.shape[2], tables_ref.shape[1]
    b = pl.program_id(0)
    length = lens_ref[b]
    n = jnp.minimum((length + block_size - 1) // block_size, w)

    def stream(slot, i):
        page = tables_ref[b, i]
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot],
                                      sems.at[slot, 1]))

    @pl.when(n > 0)
    def _warmup():
        for cp in stream(0, 0):
            cp.start()

    def fold(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n)
        def _prefetch():  # overlap the next page's DMA with this page's fold
            for cp in stream(1 - slot, i + 1):
                cp.start()

        for cp in stream(slot, i):
            cp.wait()
        out = []
        for h, (m_prev, l_prev, acc) in enumerate(carry):
            q = q_ref[0, h].astype(jnp.float32)  # (g, hd)
            k = k_buf[slot, h].astype(jnp.float32)  # (bs, hd)
            v = v_buf[slot, h].astype(jnp.float32)  # (bs, hd)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            kpos = i * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(kpos < length, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc))
        return tuple(out)

    init = tuple((jnp.full((g, 1), NEG_INF, jnp.float32),
                  jnp.zeros((g, 1), jnp.float32),
                  jnp.zeros((g, hd), jnp.float32)) for _ in range(n_kv))
    for h, (_, l, acc) in enumerate(jax.lax.fori_loop(0, n, fold, init)):
        l = jnp.where(l == 0.0, 1.0, l)  # rows with length 0 -> zeros
        o_ref[0, h] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,        # (B, H, 1, hd) - one new query per sequence
    kp: jax.Array,       # (num_blocks, KV, block_size, hd) - the key pool
    vp: jax.Array,       # (num_blocks, KV, block_size, hd) - the value pool
    tables: jax.Array,   # (B, w) int32 - physical block ids per sequence
    lengths: jax.Array,  # (B,) int32 - valid cache length per sequence
    target: Optional[HardwareTarget] = None,
    scale: Optional[float] = None,  # default 1/sqrt(hd)
) -> jax.Array:
    """Decode attention that reads K/V directly out of the paged pool.

    The communication-optimal property the paper's decode bound asks for:
    each sequence moves only its own live pages, ``ceil(len / block_size)``
    of them, once — no repeat-materialized GQA heads, no gather copy of the
    table into a contiguous buffer first, nothing past the row's length.
    The grid is one step per batch row; query heads are grouped per kv head
    (h = kv * g + i, matching the registry's GQA fold), so a row's q block
    is its (KV, g, hd) groups and each fetched page (KV, block_size, hd)
    serves every group."""
    B, H, Lq, hd = q.shape
    if Lq != 1:
        raise ValueError(f"paged decode takes one query per row, got Lq={Lq}")
    KV, block_size = kp.shape[1], kp.shape[2]
    g = H // KV
    params = pltpu.CompilerParams(vmem_limit_bytes=(
        target if target is not None else default_target()).vmem_limit_bytes)
    scale = 1.0 / (hd ** 0.5) if scale is None else scale
    qf = q.reshape(B, KV, g, hd)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, KV, g, hd), lambda b, t, l: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, KV, g, hd), lambda b, t, l: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, KV, block_size, hd), kp.dtype),
                pltpu.VMEM((2, KV, block_size, hd), vp.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        compiler_params=params,
        interpret=pallas_interpret(),
        name="paged_decode_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qf, kp, vp)
    return out.reshape(B, H, 1, hd)


# ---------------------------------------------------------------------------
# Measured HBM traffic, in 32-bit words, from launch geometry (shape-only).
# ---------------------------------------------------------------------------

def attention_hbm_words(BH: int, Lq: int, Lk: int, dh: int,
                        block_q: int, block_k: int,
                        p_q: float = 1.0, p_kv: float = 1.0,
                        p_o: float = 1.0) -> float:
    """Words the flash launch moves: q tiles once, k/v streamed once per q
    tile, o stored once — the same accounting ``plan(AttentionSpec)`` models,
    evaluated at the kernel's actual clamped/padded blocks.

    When the whole key stream is a single block (n_k == 1) the k/v index map
    (b, j, 0) is constant across the q-tile axis, so Pallas fetches k/v once
    per batch row, not once per q tile — the static auditor
    (``repro.verify``) counts index-map *transitions* and caught the
    per-q-tile formula overcounting exactly this corner."""
    bq = min(block_q, round_up(Lq, 8))
    bk = min(block_k, round_up(Lk, 8))
    lqp, lkp = round_up(Lq, bq), round_up(Lk, bk)
    n_q, n_k = lqp // bq, lkp // bk
    kv_fetches = n_q if n_k > 1 else 1
    return (p_q * BH * lqp * dh
            + 2.0 * p_kv * BH * kv_fetches * lkp * dh
            + p_o * BH * lqp * dh)


def flash_attention_access_plan(BH: int, Lq: int, Lk: int, dh: int,
                                block_q: int, block_k: int,
                                p_q: float = 1.0, p_kv: float = 1.0,
                                p_o: float = 1.0, dynamic: bool = False,
                                op: str = "attention"):
    """The :class:`repro.verify.access.KernelAccessPlan` of one flash launch
    over the folded (BH, Lq) view (``Lq`` = g * per-head queries after the
    registry's GQA fold). ``dynamic=True`` adds the scalar-prefetched
    q_offset/kv_lens operands of ``_flash_kernel_dyn`` — recorded as
    *uncounted* traffic, mirroring ``attention_hbm_words`` which charges
    only the tensor streams (2 x BH int32 words, O(BH) against O(BH*L*dh))."""
    from repro.verify.access import (BlockAccess, FlatAccess,
                                     KernelAccessPlan, ScratchAlloc)

    bq = min(block_q, round_up(Lq, 8))
    bk = min(block_k, round_up(Lk, 8))
    lqp, lkp = round_up(Lq, bq), round_up(Lk, bk)
    n_q, n_k = lqp // bq, lkp // bk
    accesses = [
        BlockAccess(name="q", kind="load", block_shape=(1, bq, dh),
                    array_shape=(BH, lqp, dh), word_size=p_q,
                    index_map=lambda b, i, j: (b, i, 0)),
        BlockAccess(name="k", kind="load", block_shape=(1, bk, dh),
                    array_shape=(BH, lkp, dh), word_size=p_kv,
                    index_map=lambda b, i, j: (b, j, 0)),
        BlockAccess(name="v", kind="load", block_shape=(1, bk, dh),
                    array_shape=(BH, lkp, dh), word_size=p_kv,
                    index_map=lambda b, i, j: (b, j, 0)),
        BlockAccess(name="out", kind="store", block_shape=(1, bq, dh),
                    array_shape=(BH, lqp, dh), word_size=p_o,
                    index_map=lambda b, i, j: (b, i, 0)),
    ]
    if dynamic:
        accesses += [
            FlatAccess(name="q_offset", kind="load", words=float(BH),
                       counted=False, note="scalar prefetch, uncharged"),
            FlatAccess(name="kv_lens", kind="load", words=float(BH),
                       counted=False, note="scalar prefetch, uncharged"),
        ]
    scratch = (
        ScratchAlloc("m/l/acc_f32", float(bq + bq + bq * dh)),
        ScratchAlloc("q_pipeline[2]", 2 * bq * dh * p_q),
        ScratchAlloc("kv_pipeline[2x2]", 4 * bk * dh * p_kv),
        ScratchAlloc("out_pipeline[2]", 2 * bq * dh * p_o),
    )
    return KernelAccessPlan(op=op, grid=(BH, n_q, n_k),
                            accesses=tuple(accesses), scratch=scratch)


def paged_decode_access_plan(B: int, KV: int, g: int, w: int,
                             block_size: int, hd: int, num_blocks: int,
                             p_q: float = 1.0, p_kv: float = 1.0,
                             p_o: float = 1.0, tables=None,
                             op: str = "attention_decode"):
    """The :class:`repro.verify.access.KernelAccessPlan` of one paged decode
    launch: grid ``(B,)``, each row's q groups in and out as blocks, and its
    page stream as ``w`` manual copies of ``(KV, block_size, hd)`` per row
    into the two-slot buffer, whose ping-pong ``dma`` declares. The stream
    is declared at full width, every row walking all ``w`` table slots: an
    upper bound, since a row reads only its ``ceil(len / block_size)`` live
    pages, and the words ``paged_decode_hbm_words`` charges. Manual copies
    elide no repeat, so any table moves these words; ``tables`` defaults to
    the allocator's normal all-distinct consecutive blocks."""
    import numpy as np

    from repro.verify.access import (BlockAccess, FlatAccess,
                                     KernelAccessPlan, ScratchAlloc,
                                     WindowAccess)
    from repro.verify.hazards import double_buffered_schedule

    if tables is None:
        tables = (np.arange(B * w, dtype=np.int64).reshape(B, w)
                  % max(num_blocks, 1))
    t = np.asarray(tables, dtype=np.int64)
    pool = (num_blocks, KV, block_size, hd)

    def page(b, i):  # the copy of table slot i: the row's i-th page, whole
        return ((t[b, i], 1), (0, KV), (0, block_size), (0, hd))

    def needs(b, i):  # positions i*bs .. (i+1)*bs of row b live in t[b, i]
        return ((t[b, i], t[b, i] + 1), (0, KV), (0, block_size), (0, hd))

    accesses = (
        BlockAccess(name="q", kind="load", block_shape=(1, KV, g, hd),
                    array_shape=(B, KV, g, hd), word_size=p_q,
                    index_map=lambda b: (b, 0, 0, 0)),
        WindowAccess(name="k_pool", kind="load", window=page, requires=needs,
                     array_shape=pool, word_size=p_kv, copies=w),
        WindowAccess(name="v_pool", kind="load", window=page, requires=needs,
                     array_shape=pool, word_size=p_kv, copies=w),
        BlockAccess(name="out", kind="store", block_shape=(1, KV, g, hd),
                    array_shape=(B, KV, g, hd), word_size=p_o,
                    index_map=lambda b: (b, 0, 0, 0)),
        FlatAccess(name="tables", kind="load", words=float(B * w),
                   note="int32 scalar prefetch, charged by words_fn"),
        FlatAccess(name="lengths", kind="load", words=float(B),
                   note="int32 scalar prefetch, charged by words_fn"),
    )
    scratch = (
        ScratchAlloc("m/l/acc_f32", float(KV * (g + g + g * hd))),
        ScratchAlloc("q_pipeline[2]", 2 * KV * g * hd * p_q),
        ScratchAlloc("kv_pages[2x2]", 4 * KV * block_size * hd * p_kv),
        ScratchAlloc("out_pipeline[2]", 2 * KV * g * hd * p_o),
    )
    return KernelAccessPlan(
        op=op, grid=(B,), accesses=accesses, scratch=scratch,
        dma=double_buffered_schedule(w, n_slots=2, name="k/v page stream"),
        note="page schedule repeats per row, cut at the row's live pages")


def paged_decode_hbm_words(B: int, KV: int, g: int, w: int, block_size: int,
                           hd: int, p_q: float = 1.0, p_kv: float = 1.0,
                           p_o: float = 1.0) -> float:
    """Words one paged decode step moves at most: each row loads its
    (KV, g, hd) query groups, streams w pages of k and v once, stores the
    groups — plus the int32 block tables and lengths (1 word each). An upper
    bound: a row reads only its ``ceil(len / block_size)`` live pages, which
    lengths known only on the device decide."""
    return (p_q * B * KV * g * hd
            + 2.0 * p_kv * B * KV * w * block_size * hd
            + p_o * B * KV * g * hd
            + B * w + B)
