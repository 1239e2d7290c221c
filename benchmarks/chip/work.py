"""The work a cell's calls require, computed from shapes alone.

These functions are the same whatever implements the call: a conv's FLOPs
and compulsory bytes (each operand read once, the output written once), and
a decoder LM's forward FLOPs for the tokens a job really processes (no
padding, no recompute).
"""

from __future__ import annotations

from typing import Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2, "int8": 1}


# ---------------------------------------------------------------------------
# Convolution (VALID padding, NCHW input, OIHW filter)
# ---------------------------------------------------------------------------

def conv_input_hw(layer: dict) -> Tuple[int, int]:
    """Input height and width a VALID conv needs for the layer's output."""
    return ((layer["h_O"] - 1) * layer["stride"] + layer["h_F"],
            (layer["w_O"] - 1) * layer["stride"] + layer["w_F"])


def conv_flops(batch: int, layer: dict) -> int:
    """Two FLOPs (multiply, add) per update of the seven nested loops."""
    return (2 * batch * layer["c_I"] * layer["c_O"] * layer["h_O"]
            * layer["w_O"] * layer["h_F"] * layer["w_F"])


def conv_bytes(batch: int, layer: dict, in_dtype: str, out_dtype: str) -> int:
    """Compulsory bytes: input and filter read once, output written once."""
    H, W = conv_input_hw(layer)
    p_in, p_out = DTYPE_BYTES[in_dtype], DTYPE_BYTES[out_dtype]
    x = batch * layer["c_I"] * H * W
    w = layer["c_O"] * layer["c_I"] * layer["h_F"] * layer["w_F"]
    y = batch * layer["c_O"] * layer["h_O"] * layer["w_O"]
    return p_in * (x + w) + p_out * y


def roofline_s(flops: float, nbytes: float, peaks) -> Tuple[float, str]:
    """(least time on the chip, which bound sets it)."""
    t_c = flops / peaks.flops_bf16
    t_m = nbytes / peaks.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ---------------------------------------------------------------------------
# Decoder LM (Qwen2-style: GQA attention, SwiGLU MLP)
# ---------------------------------------------------------------------------

def lm_layer_matmul_params(dims: dict) -> int:
    """Weights of one decoder layer that a token multiplies through."""
    D, F = dims["hidden_size"], dims["intermediate_size"]
    H, KV = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = D // H
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def lm_params(dims: dict, tied: bool) -> int:
    """Matmul parameters: embedding, layers and (untied) LM head. Norm
    scales and biases are left out, as the program's own count does."""
    V, D = dims["vocab_size"], dims["hidden_size"]
    n = V * D + dims["num_hidden_layers"] * lm_layer_matmul_params(dims)
    return n if tied else n + V * D


def lm_request_flops(dims: dict, prompt_len: int, generated: int) -> int:
    """Forward FLOPs one served request requires: every prompt token and
    every generated token but the last goes through the layers once; the
    LM head runs once per generated token (the prompt's last position, then
    each decode step); attention reads ``position + 1`` keys per token."""
    D, V = dims["hidden_size"], dims["vocab_size"]
    H, L = dims["num_attention_heads"], dims["num_hidden_layers"]
    hd = D // H
    tokens = prompt_len + max(generated, 1) - 1
    keys = tokens * (tokens + 1) // 2  # sum over positions of (pos + 1)
    per_token = 2 * L * lm_layer_matmul_params(dims)
    return (tokens * per_token + 4 * L * H * hd * keys
            + 2 * D * V * generated)


def lm_job_flops(dims: dict, served: Iterable[Tuple[int, int]]) -> int:
    """Sum of :func:`lm_request_flops` over (prompt length, generated)."""
    return sum(lm_request_flops(dims, p, g) for p, g in served)
