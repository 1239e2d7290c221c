"""Config-driven model assembly for all 10 assigned architectures.

Parameters for one *pattern unit* (e.g. 7 mamba blocks + 1 attention block for
jamba) are initialized per repeat and stacked on a leading axis; the forward
pass lax.scans over repeats so the lowered HLO contains a single unit
regardless of depth. KV caches / recurrent states are stacked the same way
and scanned alongside.

Block kinds: attn | conv (LFM2's gated short conv) | mamba | mlstm | slstm.
FFN (SwiGLU, or MoE per cfg.n_experts/moe_every/n_dense_layers) follows
attn/conv/mamba/slstm positions when d_ff > 0.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .scan_util import scan as _scan

from repro.ops import ExecutionContext

from . import moe as moe_lib
from . import ssm, xlstm
from .config import ModelConfig
from .layers import (attention_block, embed, init_attention, init_embed,
                     init_mlp, init_short_conv, lm_logits, mlp, rms_norm,
                     short_conv_block, truncated_normal)

PyTree = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _has_ffn(cfg: ModelConfig, pos: int) -> bool:
    kind = cfg.pattern[pos]
    return cfg.d_ff > 0 and kind in ("attn", "conv", "mamba", "slstm")


def _is_moe(cfg: ModelConfig, pos: int) -> bool:
    return _has_ffn(cfg, pos) and cfg.is_moe_position(pos)


def _aux_zero(cfg: ModelConfig) -> jax.Array:
    """The forward's third output starts here: the load-balancing loss, or
    a dropless expert layer's counters (``moe.moe_dropless_block``)."""
    if cfg.moe_dropless:
        return jnp.zeros((4,), jnp.int32)
    return jnp.zeros((), jnp.float32)


def init_unit(key, cfg: ModelConfig) -> Dict[str, PyTree]:
    """Parameters for one pattern unit."""
    dtype = jnp.dtype(cfg.param_dtype)
    unit: Dict[str, PyTree] = {}
    keys = jax.random.split(key, 2 * len(cfg.pattern))
    for i, kind in enumerate(cfg.pattern):
        k_core, k_ffn = keys[2 * i], keys[2 * i + 1]
        blk: Dict[str, PyTree] = {"norm1": jnp.ones((cfg.d_model,), dtype)}
        if kind == "attn":
            blk["core"] = init_attention(k_core, cfg)
        elif kind == "conv":
            blk["core"] = init_short_conv(k_core, cfg)
        elif kind == "mamba":
            blk["core"] = ssm.init_mamba(k_core, cfg)
        elif kind == "mlstm":
            blk["core"] = xlstm.init_mlstm(k_core, cfg)
        elif kind == "slstm":
            blk["core"] = xlstm.init_slstm(k_core, cfg)
        else:
            raise ValueError(f"unknown block kind {kind!r}")
        if _has_ffn(cfg, i):
            blk["norm2"] = jnp.ones((cfg.d_model,), dtype)
            blk["ffn"] = (moe_lib.init_moe(k_ffn, cfg) if _is_moe(cfg, i)
                          else init_mlp(k_ffn, cfg.d_model, cfg.d_ff, dtype))
        unit[f"b{i}"] = blk
    return unit


def init_params(key, cfg: ModelConfig) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    k_embed, k_head, k_units = jax.random.split(key, 3)
    params: Dict[str, PyTree] = {}
    if not cfg.inputs_are_embeddings or cfg.family == "vlm":
        params["embed"] = init_embed(k_embed, cfg.padded_vocab, cfg.d_model, dtype)
    params["layers"] = jax.vmap(lambda k: init_unit(k, cfg))(
        jax.random.split(k_units, cfg.repeats))
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    params["head"] = truncated_normal(k_head, (cfg.d_model, cfg.padded_vocab),
                                      cfg.d_model ** -0.5, dtype)
    return params


# ---------------------------------------------------------------------------
# Caches / recurrent state
# ---------------------------------------------------------------------------

# block kinds the paged pool serves: attention's KV in blocks, a short
# conv's state per slot
PAGED_KINDS = frozenset({"attn", "conv"})


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> PyTree:
    """Stacked (repeats-leading) cache pytree for decode."""
    def unit_cache():
        c: Dict[str, PyTree] = {}
        for i, kind in enumerate(cfg.pattern):
            if kind == "attn":
                if cfg.fused_kv_cache:
                    shape = (batch, cfg.n_kv_heads, max_len, 2, cfg.hd)
                    c[f"b{i}"] = {"kv": jnp.zeros(shape, dtype)}
                else:
                    shape = (batch, cfg.n_kv_heads, max_len, cfg.hd)
                    c[f"b{i}"] = {"k": jnp.zeros(shape, dtype),
                                  "v": jnp.zeros(shape, dtype)}
            elif kind == "conv":
                c[f"b{i}"] = {"conv": _conv_state(cfg, batch, dtype)}
            elif kind == "mamba":
                h, tail = ssm.init_mamba_state(cfg, batch, jnp.float32)
                c[f"b{i}"] = {"h": h, "tail": tail}
            elif kind == "mlstm":
                C, n = xlstm.init_mlstm_state(cfg, batch, jnp.float32)
                c[f"b{i}"] = {"C": C, "n": n}
            elif kind == "slstm":
                cc, nn = xlstm.init_slstm_state(cfg, batch, jnp.float32)
                c[f"b{i}"] = {"c": cc, "n": nn}
        return c

    one = unit_cache()
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.repeats,) + a.shape), one)


def _conv_state(cfg: ModelConfig, batch: int, dtype) -> jax.Array:
    """A gated short conv's state: its last ``K - 1`` inputs per channel."""
    return jnp.zeros((batch, cfg.conv_kernel - 1, cfg.d_model), dtype)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, quantized: bool = False,
                     batch: int = 0, head_dim: Optional[int] = None) -> PyTree:
    """Stacked paged KV pool: every attention layer gets a
    ``(num_blocks, KV, block_size, hd)`` key pool and value pool (stacked to
    (repeats, ...) like ``init_cache``). Physical block 0 is the reserved
    garbage block (``serving.kv.GARBAGE_BLOCK``): dead batch rows point their
    tables at it. Every gated short conv layer gets its per-slot state
    beside the pool, ``(batch, K - 1, D)``: a conv's state is O(1) a
    sequence and is not paged; an insert overwrites a slot's state whole,
    so a freed slot needs no reset. Other recurrent blocks (mamba, xLSTM) have
    no paged layout. ``head_dim`` is the pool's (``ops.
    pool_head_dim``): past the model's, heads are zero-padded.

    ``quantized=True`` stores the pools as int8 plus per-(block, kv_head,
    position) f32 scale leaves ``ks``/``vs`` of shape (num_blocks, KV,
    block_size) — (0.25 + 1/hd) words per cached element instead of bf16's
    0.5, which is what roughly doubles ``serving.kv.plan_pool_blocks``'s
    block capacity from the same HBM budget. Scales initialize to 1.0
    (matching the all-zero-row convention of ``quantize_symmetric``)."""
    if not set(cfg.pattern) <= PAGED_KINDS:
        raise ValueError(f"paged cache serves {sorted(PAGED_KINDS)} blocks, "
                         f"got {cfg.pattern}")
    shape = (num_blocks, cfg.n_kv_heads, block_size, head_dim or cfg.hd)
    one = {}
    for i, kind in enumerate(cfg.pattern):
        if kind == "conv":
            one[f"b{i}"] = {"conv": _conv_state(cfg, batch, dtype)}
        elif quantized:
            one[f"b{i}"] = {"kp": jnp.zeros(shape, jnp.int8),
                            "ks": jnp.ones(shape[:3], jnp.float32),
                            "vp": jnp.zeros(shape, jnp.int8),
                            "vs": jnp.ones(shape[:3], jnp.float32)}
        else:
            one[f"b{i}"] = {"kp": jnp.zeros(shape, dtype),
                            "vp": jnp.zeros(shape, dtype)}
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.repeats,) + a.shape), one)


def insert_cache_slot(cache: PyTree, row: PyTree, slot) -> PyTree:
    """Write a single-sequence cache (batch size 1) into batch slot ``slot``
    of a pooled cache. Every leaf is stacked (repeats, B, ...), so the batch
    axis is axis 1. This is the continuous-batching admission primitive:
    prefill a request at batch 1, then splice its KV/state row into the
    freed slot while the other slots keep decoding."""
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(
        lambda p, r: jax.lax.dynamic_update_slice_in_dim(
            p, r.astype(p.dtype), slot, axis=1), cache, row)


def reset_cache_slot(cache: PyTree, slot) -> PyTree:
    """Zero one batch slot of a pooled cache (slot retirement hygiene)."""
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(
        lambda p: jax.lax.dynamic_update_slice_in_dim(
            p, jnp.zeros_like(p[:, :1]), slot, axis=1), cache)


def cache_footprint_words(cfg: ModelConfig, max_len: int,
                          dtype=jnp.bfloat16,
                          block_size: Optional[int] = None) -> float:
    """Per-sequence decode-cache size in 32-bit words (the paper's unit).

    Computed from ``init_cache`` via eval_shape (no allocation); the serving
    engine divides a HardwareTarget's HBM budget by this to size its slot
    pool. ``block_size`` switches to block-granular accounting: a paged
    sequence occupies whole blocks, so its footprint is ``max_len`` rounded
    up to the block size (the engine's admission math must match actual pool
    occupancy — a shared prefix is then charged once via
    ``BlockAllocator.used_words``, not here)."""
    if block_size is not None:
        max_len = -(-max_len // block_size) * block_size
    shapes = jax.eval_shape(lambda: init_cache(cfg, 1, max_len, dtype))
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(shapes)) / 4.0


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _unit_forward(unit_params, x, cfg: ModelConfig, positions, unit_cache,
                  cache_index, n_groups: int, ctx: Optional[ExecutionContext],
                  decode: bool, attn_mask=None, block_tables=None,
                  token_mask=None):
    """One pattern unit; returns (x, new_cache, aux). ``token_mask`` marks
    the real tokens for the short conv's state and the dropless experts."""
    aux = _aux_zero(cfg)
    new_cache: Dict[str, PyTree] = {}
    for i, kind in enumerate(cfg.pattern):
        blk = unit_params[f"b{i}"]
        h = rms_norm(x, blk["norm1"], cfg.norm_eps)
        bc = unit_cache.get(f"b{i}") if unit_cache is not None else None
        if kind == "attn":
            paged = bc is not None and "kp" in bc
            quant_paged = paged and "ks" in bc  # int8 pool + scale leaves
            if bc is None:
                cache = None
            elif quant_paged:
                cache = (bc["kp"], bc["ks"], bc["vp"], bc["vs"])
            elif paged:
                cache = (bc["kp"], bc["vp"])
            elif cfg.fused_kv_cache:
                cache = (bc["kv"],)
            else:
                cache = (bc["k"], bc["v"])
            out, upd = attention_block(blk["core"], h, cfg, positions,
                                       cache=cache, cache_index=cache_index,
                                       ctx=ctx, attn_mask=attn_mask,
                                       block_tables=(block_tables if paged
                                                     else None))
            if upd is not None:
                if quant_paged:
                    new_cache[f"b{i}"] = {"kp": upd[0], "ks": upd[1],
                                          "vp": upd[2], "vs": upd[3]}
                elif paged:
                    new_cache[f"b{i}"] = {"kp": upd[0], "vp": upd[1]}
                else:
                    new_cache[f"b{i}"] = ({"kv": upd[0]} if cfg.fused_kv_cache
                                          else {"k": upd[0], "v": upd[1]})
        elif kind == "conv":
            out, upd = short_conv_block(
                blk["core"], h, cfg, state=None if bc is None else bc["conv"],
                attn_mask=token_mask, ctx=ctx)
            if upd is not None:
                new_cache[f"b{i}"] = {"conv": upd}
        elif kind == "mamba":
            state = (bc["h"], bc["tail"]) if bc is not None else None
            if decode:
                out, upd = ssm.mamba_decode_step(blk["core"], h, cfg, state,
                                                 ctx=ctx)
            else:
                out, upd = ssm.mamba_block(blk["core"], h, cfg, state,
                                           ctx=ctx)
            if upd is not None:
                new_cache[f"b{i}"] = {"h": upd[0], "tail": upd[1]}
        elif kind == "mlstm":
            state = (bc["C"], bc["n"]) if bc is not None else None
            if decode:
                out, upd = xlstm.mlstm_decode_step(blk["core"], h, cfg, state,
                                                   ctx=ctx)
            else:
                out, upd = xlstm.mlstm_block(blk["core"], h, cfg, state,
                                             ctx=ctx)
            if upd is not None:
                new_cache[f"b{i}"] = {"C": upd[0], "n": upd[1]}
        elif kind == "slstm":
            state = (bc["c"], bc["n"]) if bc is not None else None
            if decode:
                out, upd = xlstm.slstm_decode_step(blk["core"], h, cfg, state,
                                                   ctx=ctx)
            else:
                out, upd = xlstm.slstm_block(blk["core"], h, cfg, state,
                                             ctx=ctx)
            if upd is not None:
                new_cache[f"b{i}"] = {"c": upd[0], "n": upd[1]}
        x = x + out
        if _has_ffn(cfg, i):
            h = rms_norm(x, blk["norm2"], cfg.norm_eps)
            if _is_moe(cfg, i) and cfg.moe_dropless:
                f, a = moe_lib.moe_dropless_block(blk["ffn"], h, cfg,
                                                  token_mask=token_mask,
                                                  ctx=ctx)
                aux = aux + a
            elif _is_moe(cfg, i):
                f, a = moe_lib.moe_block(blk["ffn"], h, cfg,
                                         n_groups=n_groups)
                aux = aux + a
            else:
                f = mlp(blk["ffn"], h, jnp.dtype(cfg.compute_dtype))
            x = x + f
    return x, new_cache, aux


def hidden_forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,  # (B, L) int32
    embeds: Optional[jax.Array] = None,  # (B, L, D) stub-frontend outputs
    cache: Optional[PyTree] = None,
    cache_index: Optional[jax.Array] = None,
    n_groups: int = 1,
    ctx: Optional[ExecutionContext] = None,
    remat: bool = False,
    decode: bool = False,
    act_spec=None,  # PartitionSpec for (B, L, D) activations (seq parallel)
    attn_mask: Optional[jax.Array] = None,  # (B, L) True = real token
    positions: Optional[jax.Array] = None,  # (L,) or (B, L) RoPE positions
    block_tables: Optional[jax.Array] = None,  # (B, w) paged-cache tables
) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    """Backbone only: returns (final-norm hidden states, new_cache, aux).
    ``aux`` is the load-balancing loss, or with a dropless expert layer its
    counters summed over the layers (int32 (4,): pairs routed, pairs on
    held experts, grouped-matmul rows, held experts with rows).

    A ``block_tables`` array marks ``cache`` as a paged pool
    (``init_paged_cache`` layout): decode-only, one query per row, keys
    gathered through the table by the paged attention kernel.

    ``ctx`` is the execution policy (``repro.ops.ExecutionContext``): which
    backend serves each kernel call, planned against which HardwareTarget,
    at which precision. ``None`` resolves the default (XLA unless
    ``REPRO_BACKEND`` says otherwise).

    ``cache_index`` may be a scalar (all rows at one depth: training, lockstep
    prefill) or a (B,) vector (each row at its own depth: continuous-batching
    decode); positions default to ``arange(L) + cache_index`` per row.
    ``attn_mask`` marks padding (False) so attention never reads pad tokens —
    with explicit ``positions`` this makes left-padded batched prefill exact.
    Recurrent blocks (mamba/xlstm) consume every position in order, so padded
    batches are attention-arch-only; serve ragged recurrent prompts at their
    exact length (the serving engine's prefill-into-slot does)."""
    cd = jnp.dtype(cfg.compute_dtype)
    if embeds is not None:
        x = embeds.astype(cd)
    else:
        x = embed(params["embed"], tokens, cd)
    B, L, _ = x.shape
    if cache_index is None:
        cache_index = jnp.zeros((), jnp.int32)
    cache_index = jnp.asarray(cache_index, jnp.int32)
    if positions is None:
        if cache_index.ndim:  # (B,) per-slot depths -> (B, L) positions
            positions = (jnp.arange(L, dtype=jnp.int32)[None, :]
                         + cache_index[:, None])
        else:
            positions = jnp.arange(L, dtype=jnp.int32) + cache_index

    def constrain(a):
        if act_spec is not None:
            return jax.lax.with_sharding_constraint(a, act_spec)
        return a

    x = constrain(x)
    token_mask = attn_mask
    if token_mask is None and block_tables is not None:
        # a free slot's table is all reserved block 0 (serving.kv)
        token_mask = block_tables[:, :1] != 0
    body_fn = functools.partial(
        _unit_forward, cfg=cfg, positions=positions, cache_index=cache_index,
        n_groups=n_groups, ctx=ctx, decode=decode, attn_mask=attn_mask,
        block_tables=block_tables, token_mask=token_mask)

    def scan_body(carry, xs):
        x, aux = carry
        unit_params, unit_cache = xs
        x, new_cache, a = body_fn(unit_params, x, unit_cache=unit_cache)
        return (constrain(x), aux + a), new_cache

    scan_fn = scan_body
    if remat:
        scan_fn = jax.checkpoint(
            scan_body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    (x, aux), new_cache = _scan(
        scan_fn, (x, _aux_zero(cfg)),
        (params["layers"], cache) if cache is not None else (params["layers"],
                                                             _none_tree(cfg)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, (new_cache if cache is not None else None), aux


def forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    cache: Optional[PyTree] = None,
    cache_index: Optional[jax.Array] = None,
    n_groups: int = 1,
    ctx: Optional[ExecutionContext] = None,
    remat: bool = False,
    decode: bool = False,
    act_spec=None,
    attn_mask: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    """Returns (logits, new_cache, aux_loss)."""
    x, new_cache, aux = hidden_forward(
        params, cfg, tokens=tokens, embeds=embeds, cache=cache,
        cache_index=cache_index, n_groups=n_groups, ctx=ctx,
        remat=remat, decode=decode, act_spec=act_spec, attn_mask=attn_mask,
        positions=positions, block_tables=block_tables)
    logits = lm_logits(params["head"], x, jnp.dtype(cfg.compute_dtype))
    return logits, new_cache, aux


def _none_tree(cfg: ModelConfig):
    """Scan requires xs leaves; give each repeat an empty-dict placeholder."""
    return {"__empty__": jnp.zeros((cfg.repeats,), jnp.int8)}


# ---------------------------------------------------------------------------
# Losses / steps (pjit'd by launch/ and train/)
# ---------------------------------------------------------------------------

def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Causal LM shift-by-one cross entropy, mean over (B, L-1)."""
    tgt = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    true_logit = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - true_logit)


def classification_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-frame classification (encoder models)."""
    lg = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    true_logit = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - true_logit)


def chunked_next_token_loss(params, cfg: ModelConfig, x: jax.Array,
                            tokens: jax.Array, n_chunks: int) -> jax.Array:
    """Cross entropy without materializing the full (B, L, V) logits: the
    sequence is split into n_chunks, each chunk's logits are computed,
    reduced, and rematerialized in the backward pass. Essential when
    V ~ 150k (2.5 GB/device of f32 logits otherwise)."""
    B, L, D = x.shape
    xs = x[:, :-1]
    tg = tokens[:, 1:]
    Lm = xs.shape[1]
    pad = (-Lm) % n_chunks
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        tg = jnp.pad(tg, ((0, 0), (0, pad)), constant_values=-1)
    c = xs.shape[1] // n_chunks
    xs = xs.reshape(B, n_chunks, c, D).swapaxes(0, 1)
    tg = tg.reshape(B, n_chunks, c).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(xc, tc):
        lg = lm_logits(params["head"], xc, jnp.dtype(cfg.compute_dtype))
        logz = jax.nn.logsumexp(lg, axis=-1)
        tl = jnp.take_along_axis(lg, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
        valid = (tc >= 0).astype(jnp.float32)
        return jnp.sum((logz - tl) * valid), jnp.sum(valid)

    def body(carry, xs_tc):
        s, n = carry
        ls, ns = chunk_loss(*xs_tc)
        return (s + ls, n + ns), None

    (tot, cnt), _ = _scan(body, (0.0, 0.0), (xs, tg))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            n_groups: int = 1, ctx: Optional[ExecutionContext] = None,
            remat: bool = False, aux_weight: float = 0.01,
            loss_chunks: int = 0, act_spec=None):
    if loss_chunks > 1 and cfg.causal and "tokens" in batch:
        x, _, aux = hidden_forward(
            params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
            n_groups=n_groups, ctx=ctx, remat=remat,
            act_spec=act_spec)
        loss = chunked_next_token_loss(params, cfg, x, batch["tokens"],
                                       loss_chunks)
        return loss + aux_weight * aux, (loss, aux)
    logits, _, aux = forward(
        params, cfg,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        n_groups=n_groups, ctx=ctx, remat=remat,
        act_spec=act_spec)
    if cfg.causal and "tokens" in batch:
        loss = next_token_loss(logits, batch["tokens"])
    else:
        loss = classification_loss(logits, batch["labels"])
    return loss + aux_weight * aux, (loss, aux)
