"""Plain LFM2-MoE decoder in float32, its seeded weights, and its fp8 control.

Written from the published architecture (LFM2, as in the Hugging Face
``Lfm2ForCausalLM`` of transformers 4.57, with the ``lfm2_moe`` expert
layer): token embedding; per layer RMSNorm -> either a gated short
convolution (``b, c, h = split(x @ w_in, 3)``, ``y = (c * conv(b * h)) @
w_out``, the conv causal and depthwise over ``conv_L_cache`` taps, no bias)
or GQA attention (q/k RMSNorm over head_dim, then RoPE in the rotate-half
form, causal softmax scaled by 1/sqrt(head_dim), output projection) ->
residual; RMSNorm -> a SwiGLU MLP (the first ``num_dense_layers`` layers)
or the expert layer -> residual; a final RMSNorm (``embedding_norm``) and
the LM head tied to the embedding. Every matmul runs at
``Precision.HIGHEST``. It imports nothing of the program under test.

The expert layer routes as DeepSeek-V3 does with one group: the top
``num_experts_per_tok`` of ``sigmoid(x @ router) + expert_bias``, each pick
weighted by its unbiased sigmoid, divided by the picks' sum and scaled by
``routed_scaling_factor``. Of the router's ``deployment.num_experts``
outputs this chip holds ``num_experts`` (``deployment.experts_held``, the
first of them and their count); the layer adds the held picks alone, every
held expert evaluated densely on every token and weighted by its routing
weight (zero where it is not picked). What the other chips' experts would
add is left out, as in the program.

Weights are drawn from the seed by :func:`layer_weights` and
:func:`global_weights`, uniform with the stated standard deviation and
rounded to bfloat16, the type they are served in. Each leaf has its own
key per layer, and each expert's matrices their own key per expert, so the
reference regenerates one layer (and only the held experts) at a time and
the benchmark builds the program's weights with the very same values.

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
FP8_MAX = 240.0  # e4m3 as lax.reduce_precision rounds it (IEEE-style)

# leaves of each kind of layer, in key order
_CORE = {"attn": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
         "conv": ("w_in", "conv_w", "w_out")}
_FFN = {"dense": ("w_gate", "w_up", "w_down"),
        "moe": ("router", "expert_bias")}
_EXPERT = ("e_gate", "e_up", "e_down")
_NORMS = ("op_norm", "ffn_norm")
_ALL = _NORMS + _CORE["attn"] + _CORE["conv"] + _FFN["dense"] \
    + _FFN["moe"] + _EXPERT


def dims_of(config: dict) -> Dict:
    """Sizes, the layer kinds and the held share, from the configuration."""
    dep = config["deployment"]
    d = {k: config[k] for k in ("hidden_size", "intermediate_size",
                                "moe_intermediate_size", "num_hidden_layers",
                                "num_attention_heads", "num_key_value_heads",
                                "vocab_size", "num_experts_per_tok",
                                "num_dense_layers", "conv_L_cache")}
    d["head_dim"] = d["hidden_size"] // d["num_attention_heads"]
    d["router_experts"] = int(dep["num_experts"])
    d["held_first"], d["held"] = (int(v) for v in dep["experts_held"])
    if d["held"] != config["num_experts"]:
        raise ValueError("num_experts must count the experts held here")
    d["layer_types"] = tuple("attn" if t == "full_attention" else "conv"
                             for t in config["layer_types"])
    return d


def layer_kind(dims: dict, layer: int) -> Tuple[str, str]:
    """(core kind, ffn kind) of ``layer``."""
    return (dims["layer_types"][layer],
            "dense" if layer < dims["num_dense_layers"] else "moe")


def _leaf_spec(dims: dict, leaf: str) -> Tuple[Tuple[int, ...], float, float]:
    D, F, Fe = (dims["hidden_size"], dims["intermediate_size"],
                dims["moe_intermediate_size"])
    H, KV, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    K, E = dims["conv_L_cache"], dims["router_experts"]
    return {
        "op_norm": ((D,), 0.1, 1.0),
        "ffn_norm": ((D,), 0.1, 1.0),
        "wq": ((D, H * hd), D ** -0.5, 0.0),
        "wk": ((D, KV * hd), D ** -0.5, 0.0),
        "wv": ((D, KV * hd), D ** -0.5, 0.0),
        "wo": ((H * hd, D), (H * hd) ** -0.5, 0.0),
        "q_norm": ((hd,), 0.1, 1.0),
        "k_norm": ((hd,), 0.1, 1.0),
        "w_in": ((D, 3 * D), D ** -0.5, 0.0),
        "conv_w": ((K, D), K ** -0.5, 0.0),
        "w_out": ((D, D), D ** -0.5, 0.0),
        "w_gate": ((D, F), D ** -0.5, 0.0),
        "w_up": ((D, F), D ** -0.5, 0.0),
        "w_down": ((F, D), F ** -0.5, 0.0),
        "router": ((D, E), D ** -0.5, 0.0),
        "expert_bias": ((E,), 0.1, 0.0),
        "e_gate": ((D, Fe), D ** -0.5, 0.0),  # one expert's
        "e_up": ((D, Fe), D ** -0.5, 0.0),
        "e_down": ((Fe, D), Fe ** -0.5, 0.0),
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


def _leaf_key(key, leaf: str, layer):
    return jax.random.fold_in(jax.random.fold_in(key, 100 + _ALL.index(leaf)),
                              layer)


def layer_weights(key: jax.Array, dims: dict, layer, kinds=None
                  ) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights in bfloat16; an expert layer's held
    experts stacked as ``e_gate`` (n, D, Fe), ``e_up``, ``e_down``.
    ``layer`` may be traced where ``kinds`` (:func:`layer_kind`) is given."""
    core, ffn = kinds or layer_kind(dims, layer)
    out = {}
    for leaf in _NORMS + _CORE[core] + _FFN[ffn]:
        shape, std, mean = _leaf_spec(dims, leaf)
        out[leaf] = _uniform(_leaf_key(key, leaf, layer), shape, std, mean)
    if ffn == "moe":
        experts = dims["held_first"] + jnp.arange(dims["held"])
        for leaf in _EXPERT:
            shape, std, mean = _leaf_spec(dims, leaf)
            k = _leaf_key(key, leaf, layer)
            out[leaf] = jax.vmap(lambda e: _uniform(
                jax.random.fold_in(k, e), shape, std, mean))(experts)
    return out


def global_weights(key: jax.Array, dims: dict) -> Dict[str, jax.Array]:
    """Embedding (std 0.02) and final norm, in bfloat16."""
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


def _attention(h, w, dims, eps, theta, fp8):
    S, L, _ = h.shape
    H, KV, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    q = _rms_norm(_mm(h, w["wq"], fp8).reshape(S, L, H, hd), w["q_norm"], eps)
    k = _rms_norm(_mm(h, w["wk"], fp8).reshape(S, L, KV, hd), w["k_norm"], eps)
    v = _mm(h, w["wv"], fp8).reshape(S, L, KV, hd)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("shqd,shkd->shqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p, -1)
    o = jnp.einsum("shqk,shkd->shqd", p, v, precision=HIGHEST)
    return _mm(o.transpose(0, 2, 1, 3).reshape(S, L, H * hd), w["wo"], fp8)


def _short_conv(h, w, fp8):
    L = h.shape[1]
    b, c, x = jnp.split(_mm(h, w["w_in"], fp8), 3, axis=-1)
    bx = b * x
    K = w["conv_w"].shape[0]
    padded = jnp.pad(bx, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][k] * padded[:, k:k + L] for k in range(K))
    return _mm(c * conv, w["w_out"], fp8)


def _picks(h, w, dims, fp8):
    scores = jax.nn.sigmoid(_mm(h, w["router"], fp8))
    _, idx = jax.lax.top_k(scores + w["expert_bias"],
                           dims["num_experts_per_tok"])
    return scores, idx


def _flips(h, w, dims):
    """Tokens whose set of picks changes when the router's input is rounded
    to bfloat16, the program's activation type: how near to a tie routing
    runs, at one rounding."""
    _, a = _picks(h, w, dims, False)
    hb = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
    _, b = _picks(hb, w, dims, False)
    E = dims["router_experts"]
    same = jnp.all(jnp.sum(jax.nn.one_hot(a, E), -2)
                   == jnp.sum(jax.nn.one_hot(b, E), -2), axis=-1)
    return jnp.sum(~same, axis=-1)  # per sequence


def _experts(h, w, dims, fp8):
    """The held experts' part of the expert layer for every token."""
    scores, idx = _picks(h, w, dims, fp8)
    pick = jnp.take_along_axis(scores, idx, axis=-1)
    pick = pick / (jnp.sum(pick, axis=-1, keepdims=True) + 1e-20)
    local = jax.nn.one_hot(idx - dims["held_first"], dims["held"])
    gates = jnp.einsum("slk,slke->sle", pick, local)  # x routed_scaling 1
    if fp8:
        h = _fp8(h, axis=-1)
        wg, wu, wd = (jax.vmap(_fp8)(w[k]) for k in _EXPERT)
    else:
        wg, wu, wd = (w[k] for k in _EXPERT)
    g = jnp.einsum("sld,edf->slef", h, wg, precision=HIGHEST)
    u = jnp.einsum("sld,edf->slef", h, wu, precision=HIGHEST)
    a = jax.nn.silu(g) * u
    if fp8:
        a = _fp8(a, axis=-1)
    y = jnp.einsum("slef,efd->sled", a, wd, precision=HIGHEST)
    return jnp.einsum("sle,sled->sld", gates, y, precision=HIGHEST)


def _layer(x, w, dims, kinds, eps, theta, fp8: bool):
    """(the layer's output, per sequence the tokens whose expert picks
    :func:`_flips` moves; 0 in a dense layer)."""
    core, ffn = kinds
    h = _rms_norm(x, w["op_norm"], eps)
    if core == "attn":
        x = x + _attention(h, w, dims, eps, theta, fp8)
    else:
        x = x + _short_conv(h, w, fp8)
    h = _rms_norm(x, w["ffn_norm"], eps)
    if ffn == "dense":
        g, u = _mm(h, w["w_gate"], fp8), _mm(h, w["w_up"], fp8)
        return (x + _mm(jax.nn.silu(g) * u, w["w_down"], fp8),
                jnp.zeros(x.shape[0], jnp.int32))
    return x + _experts(h, w, dims, fp8), _flips(h, w, dims)


def _frozen(dims: dict):
    return tuple(sorted(dims.items()))


@functools.partial(jax.jit, static_argnames=("frozen", "kinds", "eps",
                                             "theta", "fp8"))
def _layer_step(x, key, layer, *, frozen, kinds, eps, theta, fp8):
    dims = dict(frozen)
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_weights(key, dims, layer, kinds))
    return _layer(x, w, dims, kinds, eps, theta, fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head_stats(x, final_norm, embed, tokens, *, eps, fp8):
    """At every position of one sequence ``x`` (L, D): the best logit, the
    logit of ``tokens`` (L,), and the token the model ranks first."""
    logits = _mm(_rms_norm(x, final_norm, eps)[None], embed.T, fp8)[0]
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return best, mine, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def forward_hidden(config: dict, seed: int, tokens: np.ndarray,
                   fp8: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Final hidden states (before the final norm) of ``tokens`` (S, L), and
    per sequence the router flips of :func:`_flips` summed over layers."""
    dims = dims_of(config)
    eps, theta = float(config["norm_eps"]), float(config["rope_theta"])
    key = seed_key(seed)
    embed = global_weights(key, dims)["embed"].astype(jnp.float32)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0)
    flips = jnp.zeros(x.shape[0], jnp.int32)
    for layer in range(dims["num_hidden_layers"]):
        x, f = _layer_step(x, key, jnp.int32(layer), frozen=_frozen(dims),
                           kinds=layer_kind(dims, layer), eps=eps,
                           theta=theta, fp8=fp8)
        flips = flips + f
    return x, flips


def served_gaps(config: dict, seed: int,
                served: Sequence[Tuple[np.ndarray, np.ndarray]],
                length: int, control: bool = False,
                group_tokens: int = 8192) -> Dict[str, np.ndarray]:
    """Reference logits over each prompt followed by its served tokens.

    ``served`` holds (prompt, served tokens) pairs; served token j was
    produced at position ``len(prompt) - 1 + j``. Sequences run through the
    layers in groups of a fixed shape, as many rows of ``length`` positions
    as ``group_tokens`` holds (at most one per sequence), padded at the end,
    which neither causal attention nor the causal conv lets an earlier
    position see; so the reference compiles once per layer kind.

    Returns, over all served tokens in order, ``gap``: the reference's best
    logit minus its logit of the served token (0 where they agree, never
    negative), and with ``control``: ``control_gap``, the same for the
    token the fp8 control ranks first at the same positions. ``flips`` and
    ``picks``: over the checked rows' positions (padding included) and the
    expert layers, the tokens whose picks one bfloat16 rounding of the
    router's input moves, and all the tokens routed.
    """
    dims = dims_of(config)
    eps = float(config["norm_eps"])
    glob = global_weights(seed_key(seed), dims)
    embed = glob["embed"].astype(jnp.float32)
    final_norm = glob["final_norm"].astype(jnp.float32)
    rows_per_group = max(1, min(len(served), group_tokens // length))
    gaps: List[np.ndarray] = []
    cgaps: List[np.ndarray] = []
    flips = 0
    moe_layers = dims["num_hidden_layers"] - dims["num_dense_layers"]
    for g0 in range(0, len(served), rows_per_group):
        group = served[g0:g0 + rows_per_group]
        tokens = np.zeros((rows_per_group, length), np.int32)
        for i, (p, s) in enumerate(group):
            seq = np.concatenate([p, s[:-1]])
            if len(seq) > length:
                raise ValueError(f"sequence of {len(seq)} tokens exceeds the "
                                 f"reference length {length}")
            tokens[i, :len(seq)] = seq
        x, f = forward_hidden(config, seed, tokens)
        flips += int(jnp.sum(f[:len(group)]))
        xc = (forward_hidden(config, seed, tokens, fp8=True)[0] if control
              else None)
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
    out = {"gap": np.concatenate(gaps), "flips": flips,
           "picks": len(served) * length * moe_layers}
    if control:
        out["control_gap"] = np.concatenate(cgaps)
    return out
