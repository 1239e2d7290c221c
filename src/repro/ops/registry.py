"""Backend registry: named backends, per-op entries, declared capabilities.

A :class:`Backend` is a table ``op name -> OpEntry``; each entry carries the
kernel adapter plus an :class:`OpCapabilities` declaring what call shapes it
can serve (accepted dtypes and feature flags such as ``per_row_q_offset`` or
``key_mask``). The dispatcher (``repro.ops.dispatch``) walks the requested
backend's fallback chain until an entry's capabilities cover the call — this
replaces the ad-hoc ``if use_pallas and cache is None and key_mask is None``
branches that used to live in ``models/layers.py``.

Three backends ship:

  * ``xla``    - pure jnp/lax reference path. Universal: every capability
                 flag, every dtype; the terminal fallback.
  * ``pallas`` - the LP-tiled Pallas kernels. Declares exactly what the
                 kernels support: static, traced-scalar, and per-row
                 ``q_offset`` (the flash kernel's scalar-prefetch path), plus
                 the paged ``attention_decode`` entry — so the serving decode
                 hot path runs Pallas end-to-end; only ``key_mask`` (padded
                 batched prefill) still falls back to masked XLA *by declared
                 capability*. Attention serves GQA by folding query groups
                 into the sequence axis — K/V are never materialized repeated
                 in HBM (the old wrapper's ``jnp.repeat`` cost g x the KV
                 stream traffic).
  * ``im2col`` - the paper's baseline conv algorithm (materialized patches
                 -> LP-tiled Pallas GEMM), conv2d only, falling through to
                 ``xla`` for everything else. Exists so benchmarks can
                 dispatch the algorithm the §5 tiling is measured against.

Adapters take ``(ctx, plan, *args, **kw)``: ``plan`` is the ExecutionPlan the
dispatcher resolved from the entry's ``spec_fn`` (None for ops whose tiling is
closed-form), so plan -> precision -> kernel is connected in one place. An
entry may also declare a ``words_fn`` — the measured-HBM-words counter for
the launch geometry the kernel would lower — which the dispatcher attaches to
the :class:`DispatchDecision` next to the plan's Thm 2.1 lower bound.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.core.conv_model import Precision
from repro.kernels.conv1d import (conv1d_access_plan,
                                  conv1d_causal as _conv1d_pallas,
                                  conv1d_hbm_words)
from repro.kernels.conv2d import (_conv_spec, conv2d as _conv2d_pallas,
                                  conv2d_access_plan, conv2d_hbm_words)
from repro.kernels.flash_attention import (attention_blocks,
                                           attention_hbm_words,
                                           flash_attention as _flash_pallas,
                                           flash_attention_access_plan,
                                           paged_decode_access_plan,
                                           paged_decode_attention,
                                           paged_decode_hbm_words)
from repro.kernels.grouped_matmul import (grouped_matmul as _gmm_pallas,
                                          grouped_matmul_access_plan,
                                          grouped_matmul_hbm_words)
from repro.kernels.im2col import (conv2d_im2col, im2col_access_plan,
                                  im2col_hbm_words)
from repro.kernels.matmul import (_matmul_spec, matmul as _matmul_pallas,
                                  matmul_access_plan, matmul_hbm_words)
from repro.kernels.quant import (_conv_spec_q, _matmul_spec_q,
                                 conv2d_q as _conv2d_q_pallas,
                                 conv2d_q_access_plan, conv2d_q_hbm_words,
                                 matmul_q as _matmul_q_pallas,
                                 matmul_q_access_plan, matmul_q_hbm_words)
from repro.kernels import ref
from repro.plan import AttentionSpec

from .context import ExecutionContext

# Capability flags a call can require (derived per call in dispatch.*):
#   dynamic_q_offset  - q_offset is a traced scalar (any in-cache path)
#   per_row_q_offset  - q_offset is a (B,) vector (continuous-batching decode)
#   key_mask          - a (B, Lk) validity mask over the keys (padded prefill)
ATTN_FLAGS = ("dynamic_q_offset", "per_row_q_offset", "key_mask")


@dataclasses.dataclass(frozen=True)
class OpCapabilities:
    """What one backend's op entry can serve.

    ``dtypes`` is the accepted input dtypes ("*" = anything); ``flags`` the
    supported optional call features. Entries accepting narrow storage
    (int8/fp8 streams) must declare ``accum_dtype`` — the in-kernel
    accumulation dtype, f32 or wider (lint rule VRF013)."""

    dtypes: Tuple[str, ...] = ("float32", "bfloat16")
    flags: FrozenSet[str] = frozenset()
    accum_dtype: Optional[str] = None

    def missing(self, dtype: Optional[str] = None,
                needs: Tuple[str, ...] = ()) -> Tuple[str, ...]:
        """The subset of requirements this entry cannot serve (empty = capable)."""
        out = []
        if dtype is not None and "*" not in self.dtypes and dtype not in self.dtypes:
            out.append(f"dtype:{dtype}")
        out.extend(f for f in needs if f not in self.flags)
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class OpEntry:
    """One backend's implementation of one op."""

    fn: Callable  # (ctx, plan, *args, **kw) -> result
    caps: OpCapabilities = OpCapabilities()
    # builds the planner OpSpec from the call's arrays; None = closed-form
    # tiling (conv1d lane widths, flash-attention blocks), no LP plan.
    spec_fn: Optional[Callable] = None
    # measured HBM words the kernel's launch geometry moves for this call:
    # (ctx, plan, *spec_args, **spec_kw) -> float. None = not instrumented
    # (XLA entries delegate data movement to the compiler).
    words_fn: Optional[Callable] = None
    # structured launch metadata for the static auditor: (ctx, plan,
    # *spec_args, **spec_kw) -> repro.verify.access.KernelAccessPlan. The
    # auditor abstractly interprets it and must reproduce words_fn exactly;
    # None = not statically auditable (XLA entries, and conv2d_dist whose
    # execution is a shard_map program, not one Pallas launch — its
    # shard-local conv2d entry is audited instead).
    access_plan_fn: Optional[Callable] = None
    # runtime-degradation target: the backend dispatch_call demotes to when
    # this entry raises a TransientFault at execution. None = follow the
    # backend's capability fallback. Naming an *instrumented* backend here
    # (conv2d pallas -> im2col) keeps the degraded decision priced —
    # measured_words/bound_ratio show what the demotion costs.
    degrade_to: Optional[str] = None
    # paged-pool entries that copy whole pages: each page's last axis (the
    # head dim) must fill whole lane tiles (Mosaic copies no part of one),
    # so the pool's heads are zero-padded to the target's lanes
    # (``ops.pool_head_dim``) and the entry pads q to match
    lane_pages: bool = False


@dataclasses.dataclass(frozen=True)
class Backend:
    """A named op table with a fallback chain terminating at ``xla``."""

    name: str
    ops: Dict[str, OpEntry]
    fallback: Optional[str] = None  # next backend when capabilities miss


_BACKENDS: Dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    if not backend.ops:
        raise ValueError(f"backend {backend.name!r} registers no ops")
    _BACKENDS[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; "
                       f"registered: {sorted(_BACKENDS)}")


def backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def registered_ops() -> Tuple[str, ...]:
    """Op names dispatchable from any backend (the union: every fallback
    chain terminates at ``xla``, which serves everything, so a partial
    backend like ``im2col`` widens nothing but narrows nothing either)."""
    names = set()
    for b in _BACKENDS.values():
        names |= set(b.ops)
    return tuple(sorted(names))


# ---------------------------------------------------------------------------
# XLA backend: jnp/lax implementations. Terminal fallback; supports
# everything (grouped GQA kept factored, per-row offsets, key masks).
# ---------------------------------------------------------------------------

def _xla_matmul(ctx, plan, a, b, out_dtype=jnp.float32):
    return ref.matmul_ref(a, b, out_dtype=out_dtype)


def _xla_conv2d(ctx, plan, x, w, stride=(1, 1), out_dtype=jnp.float32):
    return ref.conv2d_ref(x, w, stride=stride, out_dtype=out_dtype)


def _xla_conv1d(ctx, plan, x, w):
    return ref.conv1d_causal_ref(x, w)


def _xla_grouped_matmul(ctx, plan, x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def xla_attention(q, k, v, causal: bool = True, q_offset=0,
                  key_mask=None) -> jax.Array:
    """jnp GQA attention with the grouping kept factored (no KV repeat in HBM).

    ``q_offset`` is the absolute position of the first query: a scalar for
    lockstep batches or a (B,) vector when every row decodes at its own depth.
    ``key_mask`` is an optional (B, Lk) validity mask over the keys. Logits,
    softmax, and PV accumulate in f32 (the paper's mixed-precision
    discipline)."""
    B, H, Lq, hd = q.shape
    KV, Lk = k.shape[1], k.shape[2]
    g = H // KV
    qg = q.reshape(B, KV, g, Lq, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bkgqd,bkld->bkgql", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = None
    if causal:
        off = jnp.asarray(q_offset, jnp.int32)
        if off.ndim:
            qpos = jnp.arange(Lq, dtype=jnp.int32)[None, :] + off[:, None]
        else:
            qpos = (jnp.arange(Lq, dtype=jnp.int32) + off)[None, :]
        kpos = jnp.arange(Lk, dtype=jnp.int32)
        mask = kpos[None, None, :] <= qpos[:, :, None]  # (B|1, Lq, Lk)
    if key_mask is not None:
        km = key_mask[:, None, :]  # (B, 1, Lk)
        mask = km if mask is None else (mask & km)
    if mask is not None:
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgql,bkld->bkgqd", probs, v.astype(jnp.float32))
    return o.reshape(B, H, Lq, hd).astype(q.dtype)


def _xla_attention_entry(ctx, plan, q, k, v, causal=True, q_offset=0,
                         key_mask=None):
    return xla_attention(q, k, v, causal=causal, q_offset=q_offset,
                        key_mask=key_mask)


def _gather_attend(q, kp, vp, tables, lengths) -> jax.Array:
    """Attend over one layer's (num_blocks, KV, bs, hd) pools: gather each
    row's blocks and attend in block layout. The gather materializes a copy
    of the live cache in HBM — exactly the traffic the Pallas kernel's page
    stream avoids — but the einsums keep the (w, bs) block axes factored,
    so no transpose/reshape copies follow it. A pool whose heads are
    zero-padded past q's head dim is read up to that dim."""
    B, H, Lq, hd = q.shape
    if Lq != 1:
        raise ValueError(f"paged decode expects Lq == 1, got {Lq}")
    KV, bs = kp.shape[1], kp.shape[2]
    w = tables.shape[1]
    g = H // KV
    qg = q.reshape(B, KV, g, hd).astype(jnp.float32)
    kb = kp[tables][..., :hd]  # (B, w, KV, bs, hd)
    vb = vp[tables][..., :hd]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bkgd,bwksd->bkgws", qg,
                        kb.astype(jnp.float32)) * scale
    pos = jnp.arange(w * bs, dtype=jnp.int32).reshape(w, bs)
    mask = pos[None] < lengths[:, None, None]  # (B, w, bs)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits.reshape(B, KV, g, w * bs), axis=-1)
    o = jnp.einsum("bkgws,bwksd->bkgd", probs.reshape(B, KV, g, w, bs),
                   vb.astype(jnp.float32))
    return o.reshape(B, H, 1, hd).astype(q.dtype)


def _row_slots(tables, lengths, num_blocks: int, block_size: int):
    """(block, offset) of each row's position ``lengths - 1``: where the
    step's new k/v row lands. A row of length 0, or one past its table,
    gets block ``num_blocks``, which a ``mode="drop"`` scatter skips."""
    pos = lengths.astype(jnp.int32) - 1
    page = pos // block_size
    w = tables.shape[1]
    blk = jnp.take_along_axis(tables, jnp.clip(page, 0, w - 1)[:, None],
                              axis=1)[:, 0]
    return (jnp.where((pos >= 0) & (page < w), blk, num_blocks),
            pos % block_size)


def _put_rows(pool, layer, slots, rows):
    """``rows`` (B, KV, ...) into ``pool[layer]`` at each row's slot."""
    blk, off = slots
    return pool.at[layer, blk, :, off].set(rows.astype(pool.dtype),
                                           mode="drop")


def xla_attention_decode(q, kp, vp, tables, lengths, layer, k_new, v_new
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Reference paged decode step, the Pallas entry's contract: the
    (B, KV, hd) rows ``k_new``/``v_new`` land in layer ``layer`` of the
    stacked (layers, num_blocks, KV, bs, hp) pools at each row's position
    ``lengths - 1``, then each row attends over its ``lengths`` positions
    of that layer. Returns ``(out, kp, vp)``. The rows are zero-padded to
    a lane-padded pool's ``hp``."""
    hp = kp.shape[-1]
    pad = ((0, 0), (0, 0), (0, hp - k_new.shape[-1]))
    slots = _row_slots(tables, lengths, kp.shape[1], kp.shape[3])
    kp = _put_rows(kp, layer, slots, jnp.pad(k_new, pad))
    vp = _put_rows(vp, layer, slots, jnp.pad(v_new, pad))
    return _gather_attend(q, kp[layer], vp[layer], tables, lengths), kp, vp


def _xla_attention_decode_entry(ctx, plan, *args):
    return xla_attention_decode(*args)


# -- quantized references: integer-exact math in f32, scale applied once ----

def _xla_conv2d_q(ctx, plan, x, w, scale, stride=(1, 1),
                  out_dtype=jnp.bfloat16):
    out = ref.conv2d_ref(x.astype(jnp.float32), w.astype(jnp.float32),
                         stride=stride, out_dtype=jnp.float32)
    return (out * scale[0][None, :, None, None]).astype(out_dtype)


def _xla_matmul_q(ctx, plan, a, b, scale, out_dtype=jnp.bfloat16):
    out = ref.matmul_ref(a.astype(jnp.float32), b.astype(jnp.float32),
                         out_dtype=jnp.float32)
    return (out * scale).astype(out_dtype)


def _quantize_kv_row(r: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization of one decode step's (B, KV, hd) k or v
    row, one scale per (row, kv_head) reduced over hd — the granularity the
    quantized pool's (num_blocks, KV, bs) scale leaves store. All-zero rows
    get scale 1.0 (quantize to 0) so dequantization never divides by zero."""
    qmax = 127.0
    rf = r.astype(jnp.float32)
    amax = jnp.max(jnp.abs(rf), axis=-1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(rf / scale[..., None]), -qmax,
                 qmax).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def xla_attention_decode_quant(q, kp, ks, vp, vs, tables, lengths, layer,
                               k_new, v_new):
    """Paged decode step against an int8-quantized pool: ``kp``/``vp`` are
    stacked (layers, num_blocks, KV, bs, hd) int8 and ``ks``/``vs`` the
    matching (layers, num_blocks, KV, bs) f32 per-(block, head, position)
    scales, as the engine's quantizing insert writes them. The step's rows
    are quantized per (row, kv head) over hd and land with their scales at
    each row's position ``lengths - 1`` of layer ``layer``; dequantization
    happens in f32 on that layer's view, and the attention math is then
    exactly :func:`xla_attention_decode`'s. Returns ``(out, kp, ks, vp,
    vs)``. xla-only: every backend's fallback chain reaches it, and it
    keeps the quantized pool off the VJP path (decode is inference)."""
    slots = _row_slots(tables, lengths, kp.shape[1], kp.shape[3])
    kq, kscale = _quantize_kv_row(k_new)
    vq, vscale = _quantize_kv_row(v_new)
    kp = _put_rows(kp, layer, slots, kq)
    ks = _put_rows(ks, layer, slots, kscale)
    vp = _put_rows(vp, layer, slots, vq)
    vs = _put_rows(vs, layer, slots, vscale)
    kf = kp[layer].astype(jnp.float32) * ks[layer][..., None]
    vf = vp[layer].astype(jnp.float32) * vs[layer][..., None]
    return _gather_attend(q, kf, vf, tables, lengths), kp, ks, vp, vs


def _xla_attention_decode_quant_entry(ctx, plan, *args):
    return xla_attention_decode_quant(*args)


# -- plan-spec builders (shared by every backend's instrumented entries) ----

def _matmul_plan_spec(a, b, **kw):
    m, k = a.shape
    n = b.shape[1]
    return _matmul_spec(m, n, k, jnp.dtype(a.dtype).itemsize * 8)


def _conv2d_plan_spec(x, w, stride=(1, 1), **kw):
    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    return _conv_spec(N, c_I, c_O, (H - h_F) // sh + 1, (W - w_F) // sw + 1,
                      h_F, w_F, sh, sw, jnp.dtype(x.dtype).itemsize * 8)


def _conv2d_q_plan_spec(x, w, scale=None, stride=(1, 1),
                        out_dtype=jnp.bfloat16, **kw):
    N, c_I, H, W = x.shape
    c_O, _, h_F, w_F = w.shape
    sh, sw = stride
    return _conv_spec_q(N, c_I, c_O, (H - h_F) // sh + 1,
                        (W - w_F) // sw + 1, h_F, w_F, sh, sw,
                        x.dtype, w.dtype, out_dtype)


def _matmul_q_plan_spec(a, b, scale=None, out_dtype=jnp.bfloat16, **kw):
    m, k = a.shape
    n = b.shape[1]
    return _matmul_spec_q(m, n, k, a.dtype, b.dtype, out_dtype)


def _attention_decode_quant_plan_spec(q, kp, ks, vp, vs, tables, lengths,
                                      *step, **kw):
    """The quantized pool stream priced at its stored width: p_F counts the
    int8 block bytes plus the f32 scale per (head, position) row —
    (0.25 * hd + 1) / hd words per cached element."""
    B, H, _, hd = q.shape
    KV, bs = kp.shape[-3], kp.shape[-2]
    w = tables.shape[1]
    p_io = jnp.dtype(q.dtype).itemsize / 4.0
    p_kv = (jnp.dtype(kp.dtype).itemsize / 4.0) + 1.0 / hd
    return AttentionSpec(B=B, H=H, KV=KV, Lq=1, Lk=w * bs, hd=hd,
                         prec=Precision(p_I=p_io, p_F=p_kv, p_O=p_io))


def _attention_plan_spec(q, k, v, **kw):
    B, H, Lq, hd = q.shape
    KV, Lk = k.shape[1], k.shape[2]
    p_io = jnp.dtype(q.dtype).itemsize / 4.0
    p_kv = jnp.dtype(k.dtype).itemsize / 4.0
    return AttentionSpec(B=B, H=H, KV=KV, Lq=Lq, Lk=Lk, hd=hd,
                         prec=Precision(p_I=p_io, p_F=p_kv, p_O=p_io))


def _attention_decode_plan_spec(q, kp, vp, tables, lengths, *step, **kw):
    """Paged decode as an AttentionSpec: Lq = 1, Lk = the table window's
    token capacity (w * block_size) — the keys one decode step streams."""
    B, H, _, hd = q.shape
    KV, bs = kp.shape[-3], kp.shape[-2]
    w = tables.shape[1]
    p_io = jnp.dtype(q.dtype).itemsize / 4.0
    p_kv = jnp.dtype(kp.dtype).itemsize / 4.0
    return AttentionSpec(B=B, H=H, KV=KV, Lq=1, Lk=w * bs, hd=hd,
                         prec=Precision(p_I=p_io, p_F=p_kv, p_O=p_io))


# -- conv2d_dist: the distributed halo-exchange conv (repro.distributed) ----
#
# One op, registered on both backends: the backend picks which kernel serves
# the *shard-local* conv inside shard_map (xla -> the lax reference, pallas
# -> the PR-4 LP-tiled kernel). The entry's words_fn measures *inter-device*
# words (halo ppermute + cI psum volume per device, from the same launch
# geometry the execution lowers) — DispatchDecision.bound_ratio divides it
# by the plan's Thm 2.2/2.3 ``parallel`` bound instead of the single-device
# Thm 2.1 bound. Imports are lazy: repro.distributed dispatches back through
# repro.ops for the shard-local conv, so a top-level import would be
# circular.

def _dist_entry(local_backend: str):
    def run(ctx, plan, x, w, stride=(1, 1), out_dtype=jnp.float32,
            blocking=None, mesh=None):
        from repro.distributed.halo import halo_conv

        return halo_conv(x, w, stride=stride, blocking=blocking, mesh=mesh,
                         ctx=ctx, local_backend=local_backend,
                         out_dtype=out_dtype)
    return run


def _conv2d_dist_words(ctx, plan, x, w, stride=(1, 1), out_dtype=None,
                       blocking=None, **kw):
    from repro.distributed.halo import conv2d_dist_comm_words

    return conv2d_dist_comm_words(x, w, stride=stride, blocking=blocking,
                                  out_dtype=out_dtype or ctx.acc_dtype)


register_backend(Backend(
    name="xla",
    ops={
        "matmul": OpEntry(_xla_matmul, OpCapabilities(dtypes=("*",))),
        "conv2d": OpEntry(_xla_conv2d, OpCapabilities(dtypes=("*",))),
        "conv1d_causal": OpEntry(_xla_conv1d, OpCapabilities(dtypes=("*",))),
        "grouped_matmul": OpEntry(_xla_grouped_matmul,
                                  OpCapabilities(dtypes=("*",))),
        "attention": OpEntry(
            _xla_attention_entry,
            OpCapabilities(dtypes=("*",), flags=frozenset(ATTN_FLAGS))),
        "attention_decode": OpEntry(
            _xla_attention_decode_entry, OpCapabilities(dtypes=("*",))),
        "attention_decode_quant": OpEntry(
            _xla_attention_decode_quant_entry,
            OpCapabilities(dtypes=("*",), accum_dtype="float32"),
            spec_fn=_attention_decode_quant_plan_spec),
        "conv2d_q": OpEntry(
            _xla_conv2d_q,
            OpCapabilities(dtypes=("*",), accum_dtype="float32"),
            spec_fn=_conv2d_q_plan_spec),
        "matmul_q": OpEntry(
            _xla_matmul_q,
            OpCapabilities(dtypes=("*",), accum_dtype="float32"),
            spec_fn=_matmul_q_plan_spec),
        "conv2d_dist": OpEntry(_dist_entry("xla"), OpCapabilities(dtypes=("*",)),
                               spec_fn=_conv2d_plan_spec,
                               words_fn=_conv2d_dist_words),
    },
))


# ---------------------------------------------------------------------------
# Pallas backend: LP-tiled kernels. Plans resolve through ctx.plan (the
# process-wide cache); the kernels decide interpret mode from the platform
# (repro.plan.pallas_interpret).
#
# Differentiability: pallas_call has no JVP rule for scratch-carrying
# kernels, and the missing rule fires inside lax.scan/checkpoint jaxpr
# differentiation where no call-time feature detection could catch it. So
# every pallas entry is wrapped in jax.custom_vjp: the forward runs the
# LP-tiled kernel, the backward recomputes through the XLA reference
# implementation (the standard flash-attention fwd-kernel/bwd-recompute
# design) — training works on the pallas backend without a hand-written
# backward kernel.
# ---------------------------------------------------------------------------

def _with_xla_vjp(pallas_fn: Callable, xla_fn: Callable, *arrays):
    """Run ``pallas_fn(*arrays)`` forward with gradients defined by
    ``jax.vjp`` through ``xla_fn`` (both close over their static config)."""
    f = jax.custom_vjp(pallas_fn)

    def fwd(*arrays):
        return pallas_fn(*arrays), arrays

    def bwd(res, g):
        _, vjp = jax.vjp(xla_fn, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f(*arrays)


def _pallas_matmul(ctx, plan, a, b, out_dtype=jnp.float32):
    return _with_xla_vjp(
        lambda a_, b_: _matmul_pallas(a_, b_, out_dtype=out_dtype, plan=plan),
        lambda a_, b_: ref.matmul_ref(a_, b_, out_dtype=out_dtype), a, b)


def _pallas_conv2d(ctx, plan, x, w, stride=(1, 1), out_dtype=jnp.float32):
    return _with_xla_vjp(
        lambda x_, w_: _conv2d_pallas(x_, w_, stride=stride,
                                      out_dtype=out_dtype, plan=plan),
        lambda x_, w_: ref.conv2d_ref(x_, w_, stride=stride,
                                      out_dtype=out_dtype), x, w)


def _pallas_conv1d(ctx, plan, x, w):
    return _with_xla_vjp(
        lambda x_, w_: _conv1d_pallas(x_, w_, target=ctx.target),
        ref.conv1d_causal_ref, x, w)


def _pallas_attention(ctx, plan, q, k, v, causal=True, q_offset=0,
                      key_mask=None):
    """GQA via group-folding: queries of the g heads sharing one KV head are
    stacked along the sequence axis ((B*Hkv, g*Lq, Dh)), so K/V stream at
    their (B*Hkv, Lk, Dh) size instead of being repeated g x in HBM. The
    kernel recovers per-query absolute positions with ``q_seq_len``.

    A traced scalar or (B,) ``q_offset`` selects the flash kernel's dynamic
    path (scalar-prefetch offsets). Offsets ride as an explicit int32 operand
    through ``_with_xla_vjp`` — never closed over — so custom_vjp sees them
    as a differentiable-in-name-only arg (float0 cotangent) instead of a
    leaked tracer."""
    assert key_mask is None, "capability-gated: pallas serves no key masks"
    B, H, Lq, Dh = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    g = H // Hkv

    if isinstance(q_offset, (int, np.integer)):
        def fwd(q, k, v):
            kf = k.reshape(B * Hkv, Lk, Dh)
            vf = v.reshape(B * Hkv, Lk, Dh)
            if g == 1:
                out = _flash_pallas(q.reshape(B * H, Lq, Dh), kf, vf,
                                    causal=causal, q_offset=q_offset,
                                    target=ctx.target)
                return out.reshape(B, H, Lq, Dh)
            qf = q.reshape(B * Hkv, g * Lq, Dh)  # groups stacked on seq axis
            out = _flash_pallas(qf, kf, vf, causal=causal, q_offset=q_offset,
                                q_seq_len=Lq, target=ctx.target)
            return out.reshape(B, H, Lq, Dh)

        return _with_xla_vjp(
            fwd,
            lambda q_, k_, v_: xla_attention(q_, k_, v_, causal=causal,
                                             q_offset=q_offset), q, k, v)

    offs = jnp.asarray(q_offset, jnp.int32)
    per_row = bool(offs.ndim)
    # row b of the folded (B*Hkv) axis carries batch row b // Hkv
    row_offs = (jnp.repeat(offs, Hkv) if per_row
                else jnp.broadcast_to(offs, (B * Hkv,)))

    def fwd(q, k, v, row_offs):
        kf = k.reshape(B * Hkv, Lk, Dh)
        vf = v.reshape(B * Hkv, Lk, Dh)
        qf = q.reshape(B * Hkv, g * Lq, Dh)
        out = _flash_pallas(qf, kf, vf, causal=causal, q_offset=row_offs,
                            q_seq_len=Lq, target=ctx.target)
        return out.reshape(B, H, Lq, Dh)

    def xla_fn(q, k, v, row_offs):
        off = row_offs.reshape(B, Hkv)[:, 0] if per_row else row_offs[0]
        return xla_attention(q, k, v, causal=causal, q_offset=off)

    return _with_xla_vjp(fwd, xla_fn, q, k, v, row_offs)


def _pallas_attention_decode(ctx, plan, q, kp, vp, tables, lengths, layer,
                             k_new, v_new):
    """Paged decode on the page-streaming kernel, which writes the step's
    k/v rows into the stacked pools in place. No custom_vjp wrapper: decode
    is the serving path, and nothing differentiates through a pool update.
    A pool whose heads are lane-padded past q's (``lane_pages``) runs the
    kernel at its width, q and the rows zero-padded and the scores scaled
    by q's 1/sqrt(hd)."""
    hd, hp = q.shape[-1], kp.shape[-1]
    if hp == hd:
        return paged_decode_attention(q, kp, vp, tables, lengths, layer,
                                      k_new, v_new, target=ctx.target)

    def pad(a):
        return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, hp - hd),))

    out, kp, vp = paged_decode_attention(
        pad(q), kp, vp, tables, lengths, layer, pad(k_new), pad(v_new),
        target=ctx.target, scale=hd ** -0.5)
    return out[..., :hd], kp, vp


def _pallas_grouped_matmul(ctx, plan, x, w, group_sizes):
    """No custom_vjp wrapper: the dropless expert layer is the serving path,
    and nothing differentiates through it."""
    return _gmm_pallas(x, w, group_sizes, target=ctx.target)


def _pallas_grouped_matmul_words(ctx, plan, x, w, group_sizes, **kw):
    return grouped_matmul_hbm_words(x, w, target=ctx.target)


def _pallas_grouped_matmul_access(ctx, plan, x, w, group_sizes, **kw):
    return grouped_matmul_access_plan(x, w, target=ctx.target)


def _pallas_conv2d_q(ctx, plan, x, w, scale, stride=(1, 1),
                     out_dtype=jnp.bfloat16):
    """No custom_vjp wrapper: the quantized entries are the inference path —
    int8 operands carry no meaningful cotangent, and QAT differentiates the
    fake-quantized f32 graph, never the int8 kernel itself."""
    return _conv2d_q_pallas(x, w, scale, stride=stride, out_dtype=out_dtype,
                            plan=plan)


def _pallas_matmul_q(ctx, plan, a, b, scale, out_dtype=jnp.bfloat16):
    return _matmul_q_pallas(a, b, scale, out_dtype=out_dtype, plan=plan)


def _pallas_conv2d_q_words(ctx, plan, x, w, scale=None, stride=(1, 1),
                           out_dtype=jnp.bfloat16, **kw):
    return conv2d_q_hbm_words(x, w, scale, stride=stride, plan=plan,
                              target=ctx.target, out_dtype=out_dtype)


def _pallas_matmul_q_words(ctx, plan, a, b, scale=None,
                           out_dtype=jnp.bfloat16, **kw):
    return matmul_q_hbm_words(a, b, scale, plan=plan, target=ctx.target,
                              out_dtype=out_dtype)


def _pallas_conv2d_q_access(ctx, plan, x, w, scale=None, stride=(1, 1),
                            out_dtype=jnp.bfloat16, **kw):
    return conv2d_q_access_plan(x, w, scale, stride=stride, plan=plan,
                                target=ctx.target, out_dtype=out_dtype)


def _pallas_matmul_q_access(ctx, plan, a, b, scale=None,
                            out_dtype=jnp.bfloat16, **kw):
    return matmul_q_access_plan(a, b, scale, plan=plan, target=ctx.target,
                                out_dtype=out_dtype)


def _pallas_matmul_words(ctx, plan, a, b, out_dtype=None, **kw):
    return matmul_hbm_words(a, b, plan=plan, target=ctx.target,
                            out_dtype=out_dtype or ctx.acc_dtype)


def _pallas_conv2d_words(ctx, plan, x, w, stride=(1, 1), out_dtype=None,
                         **kw):
    return conv2d_hbm_words(x, w, stride=stride, plan=plan,
                            target=ctx.target,
                            out_dtype=out_dtype or ctx.acc_dtype)


def _pallas_attention_words(ctx, plan, q, k, v, **kw):
    B, H, Lq, Dh = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    g = H // Hkv
    p_io = jnp.dtype(q.dtype).itemsize / 4.0
    p_kv = jnp.dtype(k.dtype).itemsize / 4.0
    bq, bk = attention_blocks(Dh, ctx.target, kv_word=p_kv)
    return attention_hbm_words(B * Hkv, g * Lq, Lk, Dh, bq, bk,
                               p_q=p_io, p_kv=p_kv, p_o=p_io)


def _pallas_attention_decode_words(ctx, plan, q, kp, vp, tables, lengths,
                                   *step, **kw):
    B, H, _, _ = q.shape
    # the kernel runs at the pool's (lane_pages) width
    KV, bs, hd = kp.shape[-3:]
    p_io = jnp.dtype(q.dtype).itemsize / 4.0
    p_kv = jnp.dtype(kp.dtype).itemsize / 4.0
    return paged_decode_hbm_words(B, KV, H // KV, tables.shape[1], bs, hd,
                                  p_q=p_io, p_kv=p_kv, p_o=p_io)


# -- access plans (repro.verify): the same geometry as the words_fns, as
# structured data the static auditor can abstractly interpret -----------------

def _pallas_matmul_access(ctx, plan, a, b, out_dtype=None, **kw):
    return matmul_access_plan(a, b, plan=plan, target=ctx.target,
                              out_dtype=out_dtype or ctx.acc_dtype)


def _pallas_conv2d_access(ctx, plan, x, w, stride=(1, 1), out_dtype=None,
                          **kw):
    return conv2d_access_plan(x, w, stride=stride, plan=plan,
                              target=ctx.target,
                              out_dtype=out_dtype or ctx.acc_dtype)


def _pallas_conv1d_words(ctx, plan, x, w, **kw):
    return conv1d_hbm_words(x, w, target=ctx.target)


def _pallas_conv1d_access(ctx, plan, x, w, **kw):
    return conv1d_access_plan(x, w, target=ctx.target)


def _pallas_attention_access(ctx, plan, q, k, v, **kw):
    # the static-kernel launch over the GQA-folded view; the dynamic variant
    # adds only uncounted scalar-prefetch operands (see the builder docstring)
    B, H, Lq, Dh = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    g = H // Hkv
    p_io = jnp.dtype(q.dtype).itemsize / 4.0
    p_kv = jnp.dtype(k.dtype).itemsize / 4.0
    bq, bk = attention_blocks(Dh, ctx.target, kv_word=p_kv)
    return flash_attention_access_plan(B * Hkv, g * Lq, Lk, Dh, bq, bk,
                                       p_q=p_io, p_kv=p_kv, p_o=p_io)


def _pallas_attention_decode_access(ctx, plan, q, kp, vp, tables, lengths,
                                    *step, **kw):
    B, H, _, _ = q.shape
    # the kernel runs at the pool's (lane_pages) width
    layers, num_blocks, KV, bs, hd = kp.shape
    p_io = jnp.dtype(q.dtype).itemsize / 4.0
    p_kv = jnp.dtype(kp.dtype).itemsize / 4.0
    # concrete table values are used when available (explain() passes
    # ShapeDtypeStructs and jit passes tracers; the builder then synthesizes
    # the allocator's all-distinct table: any table moves the same words)
    try:
        t_np = np.asarray(tables, dtype=np.int64)
        if t_np.ndim != 2:
            t_np = None
    except Exception:
        t_np = None
    return paged_decode_access_plan(
        B, KV, H // KV, tables.shape[1], bs, hd, num_blocks=num_blocks,
        layers=layers, p_q=p_io, p_kv=p_kv, p_o=p_io, tables=t_np)


register_backend(Backend(
    name="pallas",
    fallback="xla",
    ops={
        "matmul": OpEntry(_pallas_matmul, spec_fn=_matmul_plan_spec,
                          words_fn=_pallas_matmul_words,
                          access_plan_fn=_pallas_matmul_access),
        # runtime faults demote to the instrumented Im2Col baseline (not
        # straight to uninstrumented xla) so the words cost of degradation
        # stays measured (conv_bench's Im2Col-vs-tiled gap)
        "conv2d": OpEntry(_pallas_conv2d, spec_fn=_conv2d_plan_spec,
                          words_fn=_pallas_conv2d_words,
                          access_plan_fn=_pallas_conv2d_access,
                          degrade_to="im2col"),
        # quantized entries: int8 streams only (f32/bf16 callers should use
        # the full-precision ops); accumulation declared per VRF013
        "conv2d_q": OpEntry(
            _pallas_conv2d_q,
            OpCapabilities(dtypes=("int8",), accum_dtype="float32"),
            spec_fn=_conv2d_q_plan_spec,
            words_fn=_pallas_conv2d_q_words,
            access_plan_fn=_pallas_conv2d_q_access),
        "matmul_q": OpEntry(
            _pallas_matmul_q,
            OpCapabilities(dtypes=("int8",), accum_dtype="float32"),
            spec_fn=_matmul_q_plan_spec,
            words_fn=_pallas_matmul_q_words,
            access_plan_fn=_pallas_matmul_q_access),
        "conv1d_causal": OpEntry(_pallas_conv1d,
                                 words_fn=_pallas_conv1d_words,
                                 access_plan_fn=_pallas_conv1d_access),
        # rows sorted by expert, groups padded to the row tile: one weight
        # stream per group with rows (the dropless expert layer)
        "grouped_matmul": OpEntry(
            _pallas_grouped_matmul,
            words_fn=_pallas_grouped_matmul_words,
            access_plan_fn=_pallas_grouped_matmul_access),
        # flash kernel: dynamic (traced scalar or per-row) q_offset rides the
        # scalar-prefetch path; only key_mask still falls back to masked xla
        # (padded batched prefill), so the decode hot path never leaves pallas.
        "attention": OpEntry(
            _pallas_attention,
            OpCapabilities(flags=frozenset({"dynamic_q_offset",
                                            "per_row_q_offset"})),
            spec_fn=_attention_plan_spec,
            words_fn=_pallas_attention_words,
            access_plan_fn=_pallas_attention_access),
        "attention_decode": OpEntry(
            _pallas_attention_decode,
            spec_fn=_attention_decode_plan_spec,
            words_fn=_pallas_attention_decode_words,
            access_plan_fn=_pallas_attention_decode_access,
            lane_pages=True),
        "conv2d_dist": OpEntry(_dist_entry("pallas"),
                               spec_fn=_conv2d_plan_spec,
                               words_fn=_conv2d_dist_words),
    },
))


# ---------------------------------------------------------------------------
# Im2Col backend: the paper's baseline conv algorithm as a third dispatchable
# conv2d entry (patches materialized in XLA, GEMM on the LP-tiled Pallas
# matmul). Every other op falls through the chain to xla.
# ---------------------------------------------------------------------------

def _im2col_conv2d(ctx, plan, x, w, stride=(1, 1), out_dtype=jnp.float32):
    return _with_xla_vjp(
        lambda x_, w_: conv2d_im2col(x_, w_, stride=stride,
                                     out_dtype=out_dtype, ctx=ctx),
        lambda x_, w_: ref.conv2d_ref(x_, w_, stride=stride,
                                      out_dtype=out_dtype), x, w)


def _im2col_conv2d_words(ctx, plan, x, w, stride=(1, 1), out_dtype=None,
                         **kw):
    return im2col_hbm_words(x, w, stride=stride, target=ctx.target,
                            out_dtype=out_dtype or ctx.acc_dtype)


def _im2col_conv2d_access(ctx, plan, x, w, stride=(1, 1), out_dtype=None,
                          **kw):
    return im2col_access_plan(x, w, stride=stride, target=ctx.target,
                              out_dtype=out_dtype or ctx.acc_dtype)


register_backend(Backend(
    name="im2col",
    fallback="xla",
    ops={
        # spec_fn resolves the same conv plan as the direct path so the
        # decision reports the identical Thm 2.1 lower bound; the GEMM's own
        # matmul plan is solved inside the kernel (memoized process-wide).
        "conv2d": OpEntry(_im2col_conv2d, spec_fn=_conv2d_plan_spec,
                          words_fn=_im2col_conv2d_words,
                          access_plan_fn=_im2col_conv2d_access),
    },
))
