"""The work of an LFM2-MoE serving job, computed from shapes and the
program's own expert counters.

A job's forward FLOPs (``job_flops``) are what its tokens require whatever
implements them: every prompt token and every generated token but the last
goes through the layers once (the short convs' and attentions' projections,
the dense FFN of the leading layers, the router, attention over
``position + 1`` keys); the LM head runs once per generated token; and the
experts held here run on the (token, expert) pairs routed to them, which
the engine counts (``ServeStats.moe_held``), with no padding.

The grouped matmul's FLOPs and compulsory bytes (``gmm_work``) follow from
its counters: ``rows`` computed (padding included, as the kernel computes
them) and ``groups`` (held experts with rows, each streaming its gate, up
and down matrices once), summed over calls; every row read and written
once per matmul.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2  # bytes


def _non_expert_layer_params(dims: dict, layer: int) -> int:
    """Weights one token multiplies through in ``layer`` outside the held
    experts (the router included)."""
    D, F = dims["hidden_size"], dims["intermediate_size"]
    H, KV, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    if dims["layer_types"][layer] == "attn":
        n = D * H * hd + 2 * D * KV * hd + H * hd * D
    else:
        n = 3 * D * D + D * D + dims["conv_L_cache"] * D
    if layer < dims["num_dense_layers"]:
        return n + 3 * D * F
    return n + D * dims["router_experts"]


def expert_pair_flops(dims: dict) -> int:
    """FLOPs of one (token, expert) pair: gate, up and down."""
    return 2 * 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def job_flops(dims: dict, served: Iterable[Tuple[int, int]],
              held_pairs: int) -> int:
    """Forward FLOPs of a job of (prompt length, generated) requests whose
    expert layers placed ``held_pairs`` pairs on the experts held here."""
    D, V = dims["hidden_size"], dims["vocab_size"]
    H, hd = dims["num_attention_heads"], dims["head_dim"]
    L = dims["num_hidden_layers"]
    n_attn = sum(1 for t in dims["layer_types"] if t == "attn")
    per_token = 2 * sum(_non_expert_layer_params(dims, i) for i in range(L))
    total = held_pairs * expert_pair_flops(dims)
    for p, g in served:
        tokens = p + max(g, 1) - 1
        keys = tokens * (tokens + 1) // 2
        total += (tokens * per_token + 4 * n_attn * H * hd * keys
                  + 2 * D * V * g)
    return total


def gmm_work(dims: dict, rows: int, groups: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of the grouped matmuls that computed ``rows`` rows
    with ``groups`` weight streams (gate, up and down each)."""
    D, Fe = dims["hidden_size"], dims["moe_intermediate_size"]
    flops = rows * expert_pair_flops(dims)
    nbytes = BF16 * (3 * groups * D * Fe + 3 * rows * (D + Fe))
    return flops, nbytes
