"""Top-k routed mixture-of-experts: GShard capacity dispatch for training,
and a dropless expert layer for serving.

Routing (:func:`route`) is softmax top-k renormalised, or DeepSeek-V3's
sigmoid rule that LFM2 uses (``cfg.router == "sigmoid_bias"``): the top-k
of ``sigmoid(logits) + expert_bias``, each pick weighted by its unbiased
sigmoid and renormalised over the picks (LFM2's routed scaling is 1).

The dropless layer (:func:`moe_dropless_block`, ``cfg.moe_dropless``) is
told which experts it holds (``cfg.experts_held``: a share of expert
parallelism), routes over all of them, keeps the (token, expert) pairs
whose expert is held, sorts them by expert into groups padded to the
grouped matmul's row tile, and runs gate/up and down through
``ops.grouped_matmul``; it then gathers each pair's row back and sums the
picks, weighted. What the experts held elsewhere would add is left out: on
one device of the deployment that partial sum goes on to the next layer.

GShard capacity dispatch (:func:`moe_block`): tokens are reshaped into G
groups (one per device shard in production; the group axis carries the
(data, model) sharding), routed top-k with a capacity limit per group, and
dispatched to experts with one-hot combine einsums — the formulation GSPMD
turns into all-to-alls when the expert axis is sharded on ``model``. Router
math is f32; dispatch/combine tensors are compute-dtype.

Capacity per group: C = ceil(k * T_g / E * capacity_factor); overflow tokens
fall through the residual (standard token dropping).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import ops
from repro.kernels.grouped_matmul import ROW_TILE
from repro.ops import ExecutionContext

from .layers import truncated_normal

Params = Dict[str, jax.Array]


def init_moe(key, cfg) -> Params:
    """Router over all ``n_experts``; expert weights for the held ones (the
    dropless layer) or for all (capacity dispatch)."""
    D, F, E = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    n = cfg.experts_held[1] if cfg.moe_dropless else E
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    std = D ** -0.5
    p = {
        "router": truncated_normal(ks[0], (D, E), std, jnp.float32),
        "w_gate": truncated_normal(ks[1], (n, D, F), std, dtype),
        "w_up": truncated_normal(ks[2], (n, D, F), std, dtype),
        "w_down": truncated_normal(ks[3], (n, F, D), F ** -0.5, dtype),
    }
    if cfg.router == "sigmoid_bias":
        p["expert_bias"] = jnp.zeros((E,), jnp.float32)
    return p


def route(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """(expert ids, weights), each (T, k), of the tokens ``x`` (T, D); the
    router runs in f32."""
    K = cfg.experts_per_token
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["expert_bias"], K)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, w
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    return idx, w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)


def dropless_rows(n_tokens: int, cfg) -> int:
    """Static row count of the grouped matmul for ``n_tokens`` tokens: every
    pair a token can place on held experts, plus a partial tile per held
    expert, in whole tiles."""
    K, n = cfg.experts_per_token, cfg.experts_held[1]
    rows = n_tokens * min(K, n) + n * (ROW_TILE - 1)
    return -(-rows // ROW_TILE) * ROW_TILE


def moe_dropless_block(
    p: Params,
    x: jax.Array,  # (B, L, D)
    cfg,
    token_mask: Optional[jax.Array] = None,  # (B, L) True = a real token
    ctx: Optional[ExecutionContext] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer for every token: (out, counters).

    Tokens outside ``token_mask`` (bucket padding, free decode rows) route
    nowhere. The counters, int32 (4,): pairs routed over all experts, pairs
    on held experts, rows the grouped matmul computes (each held expert's
    pairs rounded up to the row tile), and held experts with rows (each
    streams its weights once per matmul)."""
    B, L, D = x.shape
    T, K = B * L, cfg.experts_per_token
    lo, n = cfg.experts_held
    cd = jnp.dtype(cfg.compute_dtype)
    xt = x.reshape(T, D)
    idx, gate = route(p, xt, cfg)
    live = (jnp.ones((T,), bool) if token_mask is None
            else token_mask.reshape(T).astype(bool))
    local = (idx - lo).reshape(-1)  # (T*K,) pairs, token-major
    held = (local >= 0) & (local < n) & jnp.repeat(live, K)
    key = jnp.where(held, local, n)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    padded = -(-sizes // ROW_TILE) * ROW_TILE
    start = jnp.cumsum(sizes) - sizes
    pstart = jnp.cumsum(padded) - padded
    order = jnp.argsort(key, stable=True)
    rank = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))
    g = jnp.minimum(key, n - 1)
    M = dropless_rows(T, cfg)
    dest = jnp.where(held, pstart[g] + rank - start[g], M)
    row_token = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
    xs = xt.astype(cd)[row_token]
    hg = ops.grouped_matmul(xs, p["w_gate"].astype(cd), padded, ctx=ctx)
    hu = ops.grouped_matmul(xs, p["w_up"].astype(cd), padded, ctx=ctx)
    h = jax.nn.silu(hg.astype(jnp.float32)).astype(cd) * hu
    y = ops.grouped_matmul(h, p["w_down"].astype(cd), padded, ctx=ctx)
    # rows past the groups are unwritten: read only the held pairs' rows
    yp = jnp.where(held[:, None], y[jnp.minimum(dest, M - 1)], 0.0)
    out = jnp.einsum("tkd,tk->td", yp.reshape(T, K, D).astype(jnp.float32),
                     gate)
    counts = jnp.stack([jnp.sum(live, dtype=jnp.int32) * K,
                        jnp.sum(held, dtype=jnp.int32),
                        jnp.sum(padded), jnp.sum(sizes > 0, dtype=jnp.int32)])
    return out.reshape(B, L, D).astype(x.dtype), counts


def capacity(cfg, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.experts_per_token * cfg.capacity_factor
            / max(cfg.n_experts, 1))
    return max(4, -(-c // 4) * 4)  # multiple of 4, at least 4


def moe_block(
    p: Params,
    x: jax.Array,  # (B, L, D)
    cfg,
    n_groups: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out, aux_loss). aux_loss is the load-balancing loss. The
    one-hot dispatch and combine einsums are the formulation GSPMD shards;
    they have no kernel entry, so nothing here dispatches through ``ops``."""
    B, L, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    cd = jnp.dtype(cfg.compute_dtype)
    T = B * L
    G = n_groups if T % n_groups == 0 else 1
    Tg = T // G
    C = capacity(cfg, Tg)
    xg = x.reshape(G, Tg, D)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (G,Tg,E)

    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (G,Tg,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, k) slot within its expert's capacity buffer;
    # k=0 assignments get priority over k=1 (GShard ordering)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (G,Tg,K,E)
    kt = onehot.transpose(0, 2, 1, 3).reshape(G, K * Tg, E)  # k-major
    pos_kt = jnp.cumsum(kt, axis=1) - kt  # 0-based position per expert
    pos = pos_kt.reshape(G, K, Tg, E).transpose(0, 2, 1, 3)  # (G,Tg,K,E)
    within_cap = (pos < C).astype(jnp.float32) * onehot

    # combine[g,t,e,c] = sum_k gate_k * onehot_e * onehot_c
    pos_idx = jnp.sum(pos * onehot, axis=-1)  # (G,Tg,K) position scalar
    pos_oh = jax.nn.one_hot(pos_idx, C, dtype=jnp.float32)  # (G,Tg,K,C)
    kept = jnp.sum(within_cap, axis=-1)  # (G,Tg,K) in {0,1}
    combine = jnp.einsum("gtk,gtke,gtkc->gtec",
                         gate_vals * kept, onehot, pos_oh).astype(cd)
    dispatch = (combine != 0).astype(cd)

    if cfg.moe_shard_hints:
        # pin the GShard dispatch layout so GSPMD picks all-to-alls on the
        # G<->E reshard instead of replicating the one-hot tensors
        # (requires mesh axes "data"/"model"; launcher-only flag).
        from jax.sharding import PartitionSpec as P

        grp = ("data", "model")
        combine = jax.lax.with_sharding_constraint(
            combine, P(grp, None, None, None))
        dispatch = jax.lax.with_sharding_constraint(
            dispatch, P(grp, None, None, None))

    # dispatch -> (E, G, C, D), expert axis sharded on `model` in production
    ein = jnp.einsum("gtec,gtd->egcd", dispatch, xg.astype(cd))
    if cfg.moe_shard_hints:
        ein = jax.lax.with_sharding_constraint(
            ein, P("model", "data", None, None))
    hg = jnp.einsum("egcd,edf->egcf", ein, p["w_gate"].astype(cd))
    hu = jnp.einsum("egcd,edf->egcf", ein, p["w_up"].astype(cd))
    h = jax.nn.silu(hg.astype(jnp.float32)).astype(cd) * hu
    eout = jnp.einsum("egcf,efd->egcd", h, p["w_down"].astype(cd))
    if cfg.moe_shard_hints:
        eout = jax.lax.with_sharding_constraint(
            eout, P("model", "data", None, None))
    out = jnp.einsum("gtec,egcd->gtd", combine, eout)

    # load-balancing auxiliary loss (Switch/GShard)
    frac_tokens = jnp.mean(onehot[:, :, 0, :], axis=1)  # top-1 assignment share
    frac_probs = jnp.mean(probs, axis=1)
    aux = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
    return out.reshape(B, L, D).astype(x.dtype), aux.astype(jnp.float32)


def moe_block_dense_ref(p: Params, x: jax.Array, cfg) -> jax.Array:
    """Oracle: evaluate every expert the weights hold on every token and
    combine by the routing weights of the picks among them (no capacity
    drops) — matches moe_block when capacity_factor is large, and
    moe_dropless_block always."""
    B, L, D = x.shape
    lo, n = cfg.experts_held if cfg.moe_dropless else (0, cfg.n_experts)
    idx, w = route(p, x.reshape(B * L, D), cfg)
    gates = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(idx - lo, n))
    xf = x.reshape(B * L, D).astype(jnp.float32)
    hg = jnp.einsum("td,edf->tef", xf, p["w_gate"].astype(jnp.float32))
    hu = jnp.einsum("td,edf->tef", xf, p["w_up"].astype(jnp.float32))
    h = jax.nn.silu(hg) * hu
    eout = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(jnp.float32))
    out = jnp.einsum("te,ted->td", gates, eout)
    return out.reshape(B, L, D).astype(x.dtype)
