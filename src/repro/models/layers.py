"""Shared neural layers: norms, RoPE, SwiGLU, GQA attention with KV cache.

Pure functions over explicit parameter pytrees (no framework). Weights are
kept in cfg.param_dtype and cast to cfg.compute_dtype at use; attention
logits/softmax and all reductions accumulate in f32 (the paper's
mixed-precision discipline: low-precision operands, high-precision
accumulator).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import ops
from repro.ops import ExecutionContext

Params = Dict[str, jax.Array]


def truncated_normal(key, shape, std, dtype=jnp.float32):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, L, hd); positions: (L,) or (B, L)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # (L, hd/2)
        ang = ang[None, None]  # (1, 1, L, hd/2)
    else:
        ang = positions[:, :, None].astype(jnp.float32) * inv[None, None, :]
        ang = ang[:, None]  # (B, 1, L, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    std = d_model ** -0.5
    return {
        "w_gate": truncated_normal(k1, (d_model, d_ff), std, dtype),
        "w_up": truncated_normal(k2, (d_model, d_ff), std, dtype),
        "w_down": truncated_normal(k3, (d_ff, d_model), d_ff ** -0.5, dtype),
    }


def mlp(p: Params, x: jax.Array, compute_dtype) -> jax.Array:
    xc = x.astype(compute_dtype)
    g = jnp.einsum("...d,df->...f", xc, p["w_gate"].astype(compute_dtype))
    u = jnp.einsum("...d,df->...f", xc, p["w_up"].astype(compute_dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(compute_dtype) * u
    return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(compute_dtype)).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> Params:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    std = D ** -0.5
    p = {
        "wq": truncated_normal(ks[0], (D, H * hd), std, dtype),
        "wk": truncated_normal(ks[1], (D, KV * hd), std, dtype),
        "wv": truncated_normal(ks[2], (D, KV * hd), std, dtype),
        "wo": truncated_normal(ks[3], (H * hd, D), (H * hd) ** -0.5, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention_block(
    p: Params,
    x: jax.Array,  # (B, L, D)
    cfg,
    positions: jax.Array,  # (L,) or (B, L) absolute positions of x
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,  # (B,KV,Lmax,hd) k, v
    cache_index: Optional[jax.Array] = None,  # scalar or (B,): write offset(s)
    ctx: Optional[ExecutionContext] = None,
    attn_mask: Optional[jax.Array] = None,  # (B, L) True = real token
    block_tables: Optional[jax.Array] = None,  # (B, w): paged-pool tables
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Returns (out, updated_cache). With a cache, keys/values are written at
    cache_index and attention runs over the full cache (decode/prefill).

    ``block_tables`` switches the cache to the paged layout: ``cache`` is the
    shared ``(num_blocks, KV, block_size, hd)`` k/v pool, row i's keys live in
    blocks ``block_tables[i]``, and the step is decode-only (L == 1). The new
    K/V land in physical block ``tables[i, pos // bs]`` at offset ``pos % bs``;
    dead rows (tables all zero) write reserved garbage block 0.

    A scalar ``cache_index`` writes all rows at one offset (lockstep prefill /
    wave decode); a ``(B,)`` vector writes row i at ``cache_index[i]`` and
    masks row i's attention to ``kpos <= cache_index[i] + ...`` — the
    continuous-batching decode contract where every slot sits at its own
    depth. ``attn_mask`` marks padding tokens (False) so they are never
    attended to, fixing left-padded batched prefill at the source.

    ``ctx`` picks the attention backend via ``repro.ops`` dispatch: the
    in-cache / masked variants need capabilities (traced or per-row
    ``q_offset``, key masks) only the XLA entry declares, so a pallas
    context falls back there by capability — no per-call-site ifs here."""
    B, L, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)

    q = jnp.einsum("bld,dh->blh", xc, p["wq"].astype(cd))
    k = jnp.einsum("bld,dh->blh", xc, p["wk"].astype(cd))
    v = jnp.einsum("bld,dh->blh", xc, p["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    q = q.reshape(B, L, H, hd)
    k = k.reshape(B, L, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.reshape(B, L, KV, hd).transpose(0, 2, 1, 3)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    idx = None if cache_index is None else jnp.asarray(cache_index, jnp.int32)
    new_cache = None
    if block_tables is not None:
        if L != 1:
            raise ValueError(f"paged attention is decode-only (L == 1), got L={L}")
        quantized = len(cache) == 4  # (kp, ks, vp, vs): int8 pool + scales
        kp, vp = (cache[0], cache[2]) if quantized else cache
        bs = kp.shape[2]
        pos = jnp.broadcast_to(idx, (B,))  # per-row depth = write position
        blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None],
                                  axis=1)[:, 0]  # (B,) physical block
        off = pos % bs
        if quantized:
            # per-(row, kv_head) symmetric int8 over hd; the scale rides the
            # pool's (num_blocks, KV, bs) companion leaves
            ks, vs = cache[1], cache[3]
            k_new, ks_new = _quantize_kv_row(k[:, :, 0, :])
            v_new, vs_new = _quantize_kv_row(v[:, :, 0, :])
            kp = kp.at[blk, :, off].set(k_new)
            ks = ks.at[blk, :, off].set(ks_new)
            vp = vp.at[blk, :, off].set(v_new)
            vs = vs.at[blk, :, off].set(vs_new)
            o = ops.attention_decode_quant(q.astype(cd), kp, ks, vp, vs,
                                           block_tables, pos + 1, ctx=ctx)
            o = o.transpose(0, 2, 1, 3).reshape(B, L, H * hd)
            out = jnp.einsum("blh,hd->bld", o,
                             p["wo"].astype(cd)).astype(x.dtype)
            return out, (kp, ks, vp, vs)
        hp = kp.shape[-1]  # the pool's heads may be zero-padded past hd
        if hp != hd:
            k, v = (jnp.pad(a, ((0, 0),) * 3 + ((0, hp - hd),))
                    for a in (k, v))
        kp = kp.at[blk, :, off].set(k[:, :, 0, :].astype(kp.dtype))
        vp = vp.at[blk, :, off].set(v[:, :, 0, :].astype(vp.dtype))
        o = ops.attention_decode(q.astype(cd), kp, vp, block_tables, pos + 1,
                                 ctx=ctx)
        o = o.transpose(0, 2, 1, 3).reshape(B, L, H * hd)
        out = jnp.einsum("blh,hd->bld", o, p["wo"].astype(cd)).astype(x.dtype)
        return out, (kp, vp)
    if cache is not None and len(cache) == 1:
        # fused layout: one (B, KV, L, 2, hd) tensor -> a single
        # dynamic-update-slice per step instead of two (§Perf decode variant)
        ckv = cache[0]
        kv = jnp.stack([k, v], axis=3).astype(ckv.dtype)  # (B,KV,L,2,hd)
        if idx.ndim:  # per-slot write offsets (continuous-batching decode)
            ckv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (0, i, 0, 0)))(ckv, kv, idx)
        else:
            ckv = jax.lax.dynamic_update_slice(ckv, kv, (0, 0, idx, 0, 0))
        new_cache = (ckv,)
        k_att = ckv[:, :, :, 0, :].astype(cd)
        v_att = ckv[:, :, :, 1, :].astype(cd)
        q_offset = idx
    elif cache is not None:
        ck, cv = cache
        if idx.ndim:
            ck = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (0, i, 0)))(ck, k.astype(ck.dtype), idx)
            cv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (0, i, 0)))(cv, v.astype(cv.dtype), idx)
        else:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, idx, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, idx, 0))
        new_cache = (ck, cv)
        k_att, v_att = ck.astype(cd), cv.astype(cd)
        q_offset = idx
    else:
        k_att, v_att = k, v
        q_offset = 0

    key_mask = _expand_key_mask(attn_mask, idx, L, k_att.shape[2],
                                cached=cache is not None)
    o = ops.attention(q, k_att, v_att, causal=cfg.causal,
                      q_offset=q_offset, key_mask=key_mask, ctx=ctx)
    o = o.transpose(0, 2, 1, 3).reshape(B, L, H * hd)
    out = jnp.einsum("blh,hd->bld", o, p["wo"].astype(cd)).astype(x.dtype)
    return out, new_cache


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


def _expand_key_mask(attn_mask, idx, L: int, Lk: int, cached: bool):
    """(B, L) pad mask -> (B, Lk) key mask over this call's attention keys.

    Without a cache the keys are exactly this call's tokens. With a cache the
    keys span the whole cache; the call's mask lands on the written window
    [idx, idx + L) and everything outside it is presumed valid (unwritten
    tail entries are hidden by the causal mask)."""
    if attn_mask is None:
        return None
    attn_mask = jnp.asarray(attn_mask, bool)
    if not cached:
        return attn_mask
    if idx.ndim:
        raise NotImplementedError(
            "attn_mask with per-row cache_index is unsupported; "
            "continuous-batching decode feeds one real token per row")
    pos = jnp.arange(Lk, dtype=jnp.int32)[None, :]  # (1, Lk)
    col = jnp.clip(pos - idx, 0, L - 1)
    in_window = (pos >= idx) & (pos < idx + L)
    return jnp.where(in_window, jnp.take_along_axis(attn_mask, col, axis=1),
                     True)


# ---------------------------------------------------------------------------
# Gated short convolution (LFM2)
# ---------------------------------------------------------------------------

def init_short_conv(key, cfg) -> Params:
    D, K = cfg.d_model, cfg.conv_kernel
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    return {
        "w_in": truncated_normal(ks[0], (D, 3 * D), D ** -0.5, dtype),
        "conv_w": truncated_normal(ks[1], (K, D), K ** -0.5, dtype),
        "w_out": truncated_normal(ks[2], (D, D), D ** -0.5, dtype),
    }


def short_conv_block(
    p: Params,
    x: jax.Array,  # (B, L, D)
    cfg,
    state: Optional[jax.Array] = None,  # (B, K-1, D) inputs before x
    attn_mask: Optional[jax.Array] = None,  # (B, L) True = real token
    ctx: Optional[ExecutionContext] = None,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """LFM2's gated short convolution: ``b, c, h = split(x @ w_in, 3)``,
    then ``(c * conv(b * h)) @ w_out`` with a causal depthwise conv of ``K``
    taps through ``ops.conv1d_causal``.

    ``state`` holds the ``K - 1`` conv inputs that precede ``x`` (zeros at a
    sequence's start), so one path serves prefill and decode. The returned
    state holds the last ``K - 1`` inputs at each row's true length: ``L``,
    or the count of real tokens in ``attn_mask``. The conv is causal, so a
    right-padded prompt (a prefill bucket) is exact at its real positions
    and its state is read at the true length; pad tokens never enter it."""
    B, L, D = x.shape
    cd = jnp.dtype(cfg.compute_dtype)
    w = p["conv_w"].astype(cd)
    K = w.shape[0]
    bch = jnp.einsum("bld,de->ble", x.astype(cd), p["w_in"].astype(cd))
    b, c, h = jnp.split(bch, 3, axis=-1)
    prev = (jnp.zeros((B, K - 1, D), cd) if state is None
            else state.astype(cd))
    xp = jnp.concatenate([prev, b * h], axis=1)  # (B, K-1+L, D)
    conv = ops.conv1d_causal(xp, w, ctx=ctx)[:, K - 1:]
    out = jnp.einsum("bld,de->ble", c * conv,
                     p["w_out"].astype(cd)).astype(x.dtype)
    if state is None:
        return out, None
    # original positions n-K+1 .. n-1 sit at xp[n : n+K-1]
    n = (jnp.full((B,), L, jnp.int32) if attn_mask is None
         else jnp.sum(attn_mask, axis=1, dtype=jnp.int32))
    new = jax.vmap(lambda r, i: jax.lax.dynamic_slice_in_dim(
        r, i, K - 1, axis=0))(xp, n)
    return out, new.astype(state.dtype)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embed(key, vocab: int, d_model: int, dtype) -> jax.Array:
    return truncated_normal(key, (vocab, d_model), 1.0, dtype)


def embed(table: jax.Array, tokens: jax.Array, compute_dtype) -> jax.Array:
    return jnp.take(table, tokens, axis=0).astype(compute_dtype)


def lm_logits(head: jax.Array, x: jax.Array, compute_dtype) -> jax.Array:
    """(B, L, D) @ (D, V) -> f32 logits."""
    return jnp.einsum("bld,dv->blv", x.astype(compute_dtype),
                      head.astype(compute_dtype)).astype(jnp.float32)
