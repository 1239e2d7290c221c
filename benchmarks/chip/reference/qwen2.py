"""Plain Qwen2 decoder in float32, its seeded weights, and its fp8 control.

Written from the published architecture (Qwen2, as in the Hugging Face
``Qwen2ForCausalLM``): token embedding, per layer RMSNorm -> GQA attention
with bias on q/k/v, RoPE in the rotate-half form, causal softmax scaled by
1/sqrt(head_dim) -> output projection -> residual, RMSNorm -> SwiGLU MLP ->
residual; a final RMSNorm and the LM head, tied to the embedding where the
configuration says so. Every matmul runs at ``Precision.HIGHEST``. It
imports nothing of the program under test.

Weights are drawn from the seed by :func:`layer_weights` and
:func:`global_weights`, uniform with the stated standard deviation and
rounded to bfloat16, the type they are served in. Each leaf of each layer
has its own key, so the reference regenerates one layer at a time and the
benchmark builds the whole model on the device in one call with the very
same values.

The control (``fp8=True``) rounds every matmul operand through float8 e4m3
with a per-tensor scale for weights and a per-row scale for activations,
the precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.traffic import seed_key

HIGHEST = jax.lax.Precision.HIGHEST
# e4m3 as ``lax.reduce_precision`` rounds it (IEEE-style, largest finite
# 240): an explicit rounding op that XLA keeps, where a round trip through a
# float8 dtype may be dropped as excess precision.
FP8_MAX = 240.0

# leaf -> (shape from dims, standard deviation, mean) of one layer
_LAYER_LEAVES = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                 "mlp_norm", "w_gate", "w_up", "w_down")


def dims_of(config: dict) -> Dict[str, int]:
    d = {k: config[k] for k in ("hidden_size", "intermediate_size",
                                "num_hidden_layers", "num_attention_heads",
                                "num_key_value_heads", "vocab_size")}
    d["head_dim"] = d["hidden_size"] // d["num_attention_heads"]
    return d


def _layer_leaf_spec(dims: dict, leaf: str) -> Tuple[Tuple[int, ...], float, float]:
    D, F = dims["hidden_size"], dims["intermediate_size"]
    H, KV, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    return {
        "attn_norm": ((D,), 0.1, 1.0),
        "mlp_norm": ((D,), 0.1, 1.0),
        "wq": ((D, H * hd), D ** -0.5, 0.0),
        "wk": ((D, KV * hd), D ** -0.5, 0.0),
        "wv": ((D, KV * hd), D ** -0.5, 0.0),
        "bq": ((H * hd,), 0.1, 0.0),
        "bk": ((KV * hd,), 0.1, 0.0),
        "bv": ((KV * hd,), 0.1, 0.0),
        "wo": ((H * hd, D), (H * hd) ** -0.5, 0.0),
        "w_gate": ((D, F), D ** -0.5, 0.0),
        "w_up": ((D, F), D ** -0.5, 0.0),
        "w_down": ((F, D), F ** -0.5, 0.0),
    }[leaf]


def _uniform(key, shape, std, mean) -> jax.Array:
    """Rounded to bfloat16 by an explicit op, so that a caller which casts
    the result back to float32 inside the same jit still reads bfloat16
    values (XLA may drop a bare f32 -> bf16 -> f32 round trip)."""
    a = std * math.sqrt(3.0)
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    w = jax.lax.reduce_precision(mean + a * u, exponent_bits=8,
                                 mantissa_bits=7)
    return w.astype(jnp.bfloat16)


def layer_weights(key: jax.Array, dims: dict, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights in bfloat16 (``layer`` may be traced)."""
    out = {}
    for i, leaf in enumerate(_LAYER_LEAVES):
        shape, std, mean = _layer_leaf_spec(dims, leaf)
        k = jax.random.fold_in(jax.random.fold_in(key, 100 + i), layer)
        out[leaf] = _uniform(k, shape, std, mean)
    return out


def global_weights(key: jax.Array, dims: dict) -> Dict[str, jax.Array]:
    """Embedding (std 0.02, the published initializer range) and final norm,
    in bfloat16."""
    D, V = dims["hidden_size"], dims["vocab_size"]
    return {"embed": _uniform(jax.random.fold_in(key, 1), (V, D), 0.02, 0.0),
            "final_norm": _uniform(jax.random.fold_in(key, 2), (D,), 0.1, 1.0)}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fp8(a: jax.Array, axis=None) -> jax.Array:
    """Round through float8 e4m3 with a scale per tensor (``axis=None``) or
    per slice along ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return jax.lax.reduce_precision(a / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x, axis=-1), _fp8(w)
    return jnp.einsum("sld,de->sle", x, w, precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):  # x (S, H, L, hd)
    hd, L = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _layer(x, w, dims, eps, theta, fp8: bool):
    S, L, D = x.shape
    H, KV, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    h = _rms_norm(x, w["attn_norm"], eps)
    q = (_mm(h, w["wq"], fp8) + w["bq"]).reshape(S, L, H, hd).transpose(0, 2, 1, 3)
    k = (_mm(h, w["wk"], fp8) + w["bk"]).reshape(S, L, KV, hd).transpose(0, 2, 1, 3)
    v = (_mm(h, w["wv"], fp8) + w["bv"]).reshape(S, L, KV, hd).transpose(0, 2, 1, 3)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = H // KV
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("shqd,shkd->shqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p, -1)
    o = jnp.einsum("shqk,shkd->shqd", p, v, precision=HIGHEST)
    x = x + _mm(o.transpose(0, 2, 1, 3).reshape(S, L, H * hd), w["wo"], fp8)
    h = _rms_norm(x, w["mlp_norm"], eps)
    g = _mm(h, w["w_gate"], fp8)
    u = _mm(h, w["w_up"], fp8)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "fp8"))
def _layer_step(x, key, layer, *, dims, eps, theta, fp8):
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_weights(key, dict(dims), layer))
    return _layer(x, w, dict(dims), eps, theta, fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head_stats(x, final_norm, embed, tokens, *, eps, fp8):
    """At every position of one sequence ``x`` (L, D): the best logit, the
    logit of ``tokens`` (L,), and the token the model ranks first."""
    logits = _mm(_rms_norm(x, final_norm, eps)[None], embed.T, fp8)[0]
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return best, mine, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def served_gaps(config: dict, seed: int,
                served: Sequence[Tuple[np.ndarray, np.ndarray]],
                length: int, control: bool = False,
                group_tokens: int = 8192) -> Dict[str, np.ndarray]:
    """Reference logits over each prompt followed by its served tokens.

    ``served`` holds (prompt, served tokens) pairs; served token j was
    produced at position ``len(prompt) - 1 + j``. Sequences run through the
    layers in groups of a fixed shape, as many rows of ``length`` positions
    as ``group_tokens`` holds (at most one per sequence), padded at the end,
    which causal attention never lets an earlier position see; so the
    reference compiles once per cell.

    Returns, over all served tokens in order, ``gap``: the reference's best
    logit minus its logit of the served token (0 where they agree, never
    negative), and with ``control``: ``control_gap``, the same for the
    token the fp8 control ranks first at the same positions.
    """
    dims = dims_of(config)
    frozen = tuple(sorted(dims.items()))
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    key = seed_key(seed)
    glob = global_weights(key, dims)
    embed = glob["embed"].astype(jnp.float32)
    final_norm = glob["final_norm"].astype(jnp.float32)
    rows_per_group = max(1, min(len(served), group_tokens // length))
    gaps: List[np.ndarray] = []
    cgaps: List[np.ndarray] = []
    for g0 in range(0, len(served), rows_per_group):
        group = served[g0:g0 + rows_per_group]
        tokens = np.zeros((rows_per_group, length), np.int32)
        for i, (p, s) in enumerate(group):
            seq = np.concatenate([p, s[:-1]])
            if len(seq) > length:
                raise ValueError(f"sequence of {len(seq)} tokens exceeds the "
                                 f"reference length {length}")
            tokens[i, :len(seq)] = seq
        x = jnp.take(embed, jnp.asarray(tokens), axis=0)
        xc = x if control else None
        for layer in range(dims["num_hidden_layers"]):
            x = _layer_step(x, key, layer, dims=frozen, eps=eps, theta=theta,
                            fp8=False)
            if control:
                xc = _layer_step(xc, key, layer, dims=frozen, eps=eps,
                                 theta=theta, fp8=True)
        for i, (p, s) in enumerate(group):
            at = slice(len(p) - 1, len(p) - 1 + len(s))
            want = np.zeros(length, np.int32)
            want[at] = s
            best, mine, _ = _head_stats(x[i], final_norm, embed,
                                        jnp.asarray(want), eps=eps, fp8=False)
            gaps.append(np.asarray(best - mine)[at])
            if control:
                _, _, pick = _head_stats(xc[i], final_norm, embed,
                                         jnp.asarray(want), eps=eps, fp8=True)
                _, theirs, _ = _head_stats(x[i], final_norm, embed, pick,
                                           eps=eps, fp8=False)
                cgaps.append(np.asarray(best - theirs)[at])
    out = {"gap": np.concatenate(gaps)}
    if control:
        out["control_gap"] = np.concatenate(cgaps)
    return out
