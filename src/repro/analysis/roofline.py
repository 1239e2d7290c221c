"""Roofline terms from compiled dry-run artifacts (no hardware required).

    compute term    = HLO_FLOPs / (chips * peak_FLOPs)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = wire_bytes_per_chip / link_bw

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis(). Collective bytes
are NOT in cost_analysis: we parse the post-SPMD module text and sum the
result sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, with ring-model wire multipliers (all-reduce 2x).

Hardware model: the published peaks of one chip, looked up by the
``jax.Device.device_kind`` a run models (:data:`PEAKS`). A kind the table
does not hold is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per inter-chip link


V5E_KIND = "TPU v5 lite"  # what jax reports as a v5e's device_kind

# ``jax.Device.device_kind`` -> peaks. Source: Google Cloud documentation,
# "TPU v5e": per chip 197 TFLOP/s bf16, 819 GB/s of HBM bandwidth, and
# 1,600 Gbit/s of inter-chip interconnect over four links.
PEAKS: Dict[str, ChipPeaks] = {
    V5E_KIND: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                        ici_bw=1600e9 / 8 / 4),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The :data:`PEAKS` entry of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


# The unit every words_fn / bound / static audit counts in: 32-bit words.
WORD_BYTES = 4


def words_to_bytes(words, dtypes=None):
    """32-bit words (the paper's and ``repro.verify``'s unit) -> bytes.

    Scalar form (``dtypes`` omitted): ``words`` is a dtype-weighted word
    count — every ``words_fn`` and static-audit total is already priced in
    32-bit words, so bytes are a flat ``words * 4``.

    Per-operand form: ``words`` is a mapping operand -> ELEMENT count and
    ``dtypes`` the plan's per-operand dtype map (``ExecutionPlan.dtypes``
    pairs, or a dict) as carried by plan format v5. Each operand converts at
    its own storage width — an int8 input stream moves 1 byte per element
    where the f32 view would charge 4 — and a dict of per-operand bytes
    comes back. Operands absent from the map price as float32.
    """
    if dtypes is None:
        return float(words) * WORD_BYTES
    from repro.quant.spec import dtype_words
    dmap = dict(dtypes)
    out = {}
    for operand, elems in words.items():
        dt = dmap.get(operand, "float32")
        try:
            w = dtype_words(dt)
        except ValueError:
            w = 1.0  # "words:<x>" placeholders from exotic plan widths
            if dt.startswith("words:"):
                w = float(dt.split(":", 1)[1])
        out[operand] = float(elems) * w * WORD_BYTES
    return out


def hbm_seconds(words: float, chips: int = 1) -> float:
    """Roofline memory time for a word count on v5e chips (what the
    planner's ``TPU_V5E`` target describes) — the bridge from the static
    auditor's exact HBM words to the same time model the dry-run rooflines
    use (``memory_s = bytes / (chips * hbm_bw)``)."""
    return words_to_bytes(words) / (chips * PEAKS[V5E_KIND].hbm_bw)


# Per-DMA-transfer issue latency (descriptor setup + dispatch), the alpha of
# the alpha-beta model below. ~2us is the order of a TPU async-copy issue; the
# exact constant only has to rank tile candidates, not predict wall clock.
DMA_SETUP_SECONDS = 2e-6


def alpha_beta_seconds(words: float, transfers: float, chips: int = 1
                       ) -> float:
    """Latency + bandwidth (alpha-beta) roofline for one kernel launch:
    ``hbm_seconds(words)`` (the bandwidth term every words_fn prices) plus
    ``transfers`` DMA issues at ``DMA_SETUP_SECONDS`` each. This is the
    offline cost model of ``repro.plan.autotune``: the blocking LP minimizes
    the bandwidth term alone, so near-bound tile candidates that trade a few
    percent more words for far fewer (bigger) DMA transfers rank faster here
    — exactly the frontier a measured autotuner exists to explore."""
    return hbm_seconds(words, chips) + float(transfers) * DMA_SETUP_SECONDS

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# wire multiplier per result byte (ring model)
_WIRE_MULT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
              "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|tuple\([^)]*\)|[\w\[\],{}]+)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-collective-kind wire bytes (per device, post-SPMD local shapes).
    '-done' ops are skipped so async pairs aren't double counted."""
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "-done(" in s or "-done.1" in s:
            continue
        m = _OP_RE.search(s)
        if not m:
            continue
        kind = m.group(1)
        lhs = s.split("=", 1)[0]
        rhs_head = s.split("=", 1)[1]
        # result type appears right after '=' (e.g.  %x = bf16[8,128]{1,0} all-reduce(...)
        head = rhs_head.split(kind)[0]
        nbytes = _shape_bytes(head)
        if nbytes == 0:  # fall back: operand types inside parens
            nbytes = _shape_bytes(s[m.end():])
        out[kind] += nbytes * _WIRE_MULT[kind]
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    wire_bytes_per_chip: float
    collectives: Dict[str, float]
    model_flops: float
    bytes_per_device: Dict[str, float]
    device_kind: str = V5E_KIND  # the chip the dry run compiles for

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * self.peaks.flops_bf16)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * self.peaks.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.wire_bytes_per_chip / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs: how much of compiled compute is useful."""
        return self.model_flops / max(self.hlo_flops, 1.0)

    @property
    def mfu(self) -> float:
        """Model FLOPs / (roofline step time * peak): the roofline-fraction
        score (upper bounds real MFU)."""
        return self.model_flops / (
            self.step_time_s * self.chips * self.peaks.flops_bf16 + 1e-30)

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "wire_bytes_per_chip": self.wire_bytes_per_chip,
            "collectives": self.collectives,
            "model_flops": self.model_flops,
            "bytes_per_device": self.bytes_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "step_time_s": self.step_time_s,
            "useful_flops_frac": self.useful_flops_frac,
            "mfu": self.mfu,
        }


def model_flops(arch: str, shape_name: str) -> float:
    """6*N_active*tokens for training; 2*N_active*tokens for serving."""
    from repro.configs import get_config
    from repro.models.config import LM_SHAPES

    cfg = get_config(arch)
    shape = LM_SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build(arch: str, shape_name: str, mesh_name: str, chips: int,
          cost: dict, mem: dict, hlo_text: str) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=flops * chips if flops > 0 else 0.0,
        hlo_bytes=nbytes * chips if nbytes > 0 else 0.0,
        wire_bytes_per_chip=coll["total"],
        collectives=coll,
        model_flops=model_flops(arch, shape_name),
        bytes_per_device=mem,
    )
