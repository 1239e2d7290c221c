"""Hardware targets: one declarative description of the memory hierarchy the
blocking LP optimizes against (paper §3.2/§5) plus the device mesh the
parallel LP shards over (paper §4.2).

A ``HardwareTarget`` is the single input every planner consumer constructs —
kernels, launchers, benchmarks, and serving all describe *where* they run with
this dataclass and let ``repro.plan.plan`` decide *how* (tiles, grids,
shardings). It subsumes the ad-hoc ``MemoryModel`` constructions that used to
be scattered across ``kernels/*`` and ``benchmarks/*``.

Presets:
  * ``TPU_V5E``      - 16 MiB unified VMEM, bf16 streams / f32 accumulate,
                       MXU (8, 128) alignment.
  * ``GEMMINI``      - the paper's §5 accelerator: 256 KiB scratchpad (int8)
                       + 64 KiB accumulator (f32), split-buffer mode.
  * ``CPU_INTERPRET``- the CPU: Pallas runs in interpret mode, f32, and no
                       tile alignment (the interpreter has no (8, 128) tiles).

Where Pallas runs is not a property of a target: :func:`pallas_interpret`
decides it from the backend the kernels execute on, and
:func:`default_target` maps the attached device to its preset.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax

from repro.core.conv_model import BF16_ACC32, FP32, INT8_ACC32, Precision
from repro.core.tiling import MemoryModel, TPU_VMEM_WORDS
from repro.quant.spec import PrecisionSpec

MeshAxes = Tuple[Tuple[str, int], ...]


@dataclasses.dataclass(frozen=True)
class HardwareTarget:
    """Full memory-hierarchy + mesh description of one deployment target.

    Capacities are in the paper's unit (words of 32 bits). ``mesh_axes`` is
    empty for single-device targets; a non-empty tuple makes ``plan`` attach a
    ``ShardingPlan`` (PartitionSpecs) to the returned ``ExecutionPlan``.
    """

    name: str
    vmem_words: float = float(TPU_VMEM_WORDS)  # scratchpad / cache / VMEM
    acc_words: Optional[float] = None  # separate accumulator ("split" only)
    hbm_words: float = 4 * 2**30 / 4.0  # main-memory capacity (KV-cache pool)
    memory: str = "unified"  # "unified" | "split" (paper eq. 6 vs §5)
    double_buffer: bool = True  # §5: halves usable capacity
    precision: Precision = BF16_ACC32  # default when the OpSpec has none
    use_pallas: bool = False  # whether consumers should take the Pallas path
    mesh_axes: MeshAxes = ()  # ((name, size), ...) for multi-device targets
    align_sublane: int = 8  # MXU sublane multiple (1 = no alignment)
    align_lane: int = 128  # MXU lane multiple (1 = no alignment)
    # Optional quantized storage policy (repro.quant). When set, consumers
    # that opt into the quantized path (ops.conv2d_q / matmul_q callers, the
    # serving engine's kv_dtype knob) read the per-operand dtypes from here;
    # its ``.precision`` projection is what the LP and bounds then price.
    # ``precision`` above stays the full-precision default for ops that
    # don't quantize.
    quant: Optional[PrecisionSpec] = None

    @property
    def align(self) -> Tuple[int, int]:
        """The ``(sublane, lane)`` alignment this target's kernels lay out
        under."""
        return (self.align_sublane, self.align_lane)

    @property
    def vmem_limit_bytes(self) -> int:
        """The scoped-VMEM limit every Pallas kernel is compiled with. The LP
        fits one copy of each tile into half of ``vmem_words`` (§5 double
        buffering); the kernels hold two copies of their streamed tiles plus
        Pallas' own output buffers and Mosaic's matmul scratch, none of which
        the LP prices. So the compiler is granted twice the planned capacity
        (32 MiB for ``TPU_V5E``, of the v5e core's 128 MiB of VMEM)."""
        return int(2 * self.vmem_words * 4)

    def memory_model(self) -> MemoryModel:
        """The capacity model the blocking LP consumes."""
        return MemoryModel(M=self.vmem_words, M_acc=self.acc_words,
                           mode=self.memory, double_buffer=self.double_buffer)

    @property
    def n_devices(self) -> int:
        out = 1
        for _, size in self.mesh_axes:
            out *= size
        return out

    # -- builders -------------------------------------------------------------
    def with_mesh(self, axes: Sequence[Tuple[str, int]]) -> "HardwareTarget":
        return dataclasses.replace(
            self, mesh_axes=tuple((str(n), int(s)) for n, s in axes))

    def with_precision(self, prec: Precision) -> "HardwareTarget":
        return dataclasses.replace(self, precision=prec)

    def with_vmem(self, vmem_words: float) -> "HardwareTarget":
        return dataclasses.replace(self, vmem_words=float(vmem_words))

    def with_quant(self, spec: Optional[PrecisionSpec]) -> "HardwareTarget":
        """Attach (or clear, with None) a quantized storage policy."""
        return dataclasses.replace(self, quant=spec)

    @classmethod
    def from_mesh(cls, mesh: Any, base: Optional["HardwareTarget"] = None
                  ) -> "HardwareTarget":
        """Target whose mesh_axes mirror a ``jax.sharding.Mesh``."""
        base = base or TPU_V5E
        return base.with_mesh(tuple(zip(mesh.axis_names, mesh.devices.shape)))

    # -- (de)serialization ----------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "vmem_words": self.vmem_words,
            "acc_words": self.acc_words,
            "hbm_words": self.hbm_words,
            "memory": self.memory,
            "double_buffer": self.double_buffer,
            "precision": list(self.precision.as_tuple()),
            "use_pallas": self.use_pallas,
            "mesh_axes": [list(ax) for ax in self.mesh_axes],
            "align_sublane": self.align_sublane,
            "align_lane": self.align_lane,
            "quant": None if self.quant is None else self.quant.to_dict(),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "HardwareTarget":
        # plan files written before the platform rule carry an "interpret"
        # key; where Pallas runs is no longer a property of the target
        return cls(
            name=d["name"],
            vmem_words=float(d["vmem_words"]),
            acc_words=None if d.get("acc_words") is None else float(d["acc_words"]),
            hbm_words=float(d.get("hbm_words", 4 * 2**30 / 4.0)),
            memory=d.get("memory", "unified"),
            double_buffer=bool(d.get("double_buffer", True)),
            precision=Precision(*d.get("precision", (0.5, 0.5, 1.0))),
            use_pallas=bool(d.get("use_pallas", False)),
            mesh_axes=tuple((str(n), int(s)) for n, s in d.get("mesh_axes", ())),
            align_sublane=int(d.get("align_sublane", 8)),
            align_lane=int(d.get("align_lane", 128)),
            quant=(None if d.get("quant") is None
                   else PrecisionSpec.from_dict(d["quant"])),
        )


# ---------------------------------------------------------------------------
# Presets.
# ---------------------------------------------------------------------------

TPU_V5E = HardwareTarget(
    name="tpu_v5e",
    vmem_words=float(TPU_VMEM_WORDS),
    hbm_words=16 * 2**30 / 4.0,  # 16 GiB HBM per v5e chip
    memory="unified",
    precision=BF16_ACC32,
    use_pallas=True,
)

# GEMMINI defaults from the paper §5: 256 KiB scratchpad of 8-bit words and a
# 64 KiB accumulator of 32-bit words, both double buffered. No MXU lane
# alignment — the systolic array constraint is folded into the LP capacities.
GEMMINI = HardwareTarget(
    name="gemmini",
    vmem_words=256 * 1024 / 4.0,
    acc_words=64 * 1024 / 4.0,
    hbm_words=2**30 / 4.0,  # 1 GiB FireSim DRAM

    memory="split",
    precision=INT8_ACC32,
    use_pallas=False,
    align_sublane=1,
    align_lane=1,
)

CPU_INTERPRET = HardwareTarget(
    name="cpu_interpret",
    vmem_words=float(TPU_VMEM_WORDS),
    memory="unified",
    precision=FP32,
    use_pallas=False,
    align_sublane=1,
    align_lane=1,
)

TARGETS: Dict[str, HardwareTarget] = {
    t.name: t for t in (TPU_V5E, GEMMINI, CPU_INTERPRET)
}


# ``jax.Device.device_kind`` -> the preset that describes that device.
TARGET_BY_DEVICE_KIND: Dict[str, HardwareTarget] = {"TPU v5 lite": TPU_V5E}


def default_target() -> HardwareTarget:
    """The preset of the device JAX runs on: ``CPU_INTERPRET`` on the CPU,
    the ``TARGET_BY_DEVICE_KIND`` entry on an accelerator. An accelerator
    this table does not describe is an error, never a silent default."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_INTERPRET
    try:
        return TARGET_BY_DEVICE_KIND[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware target describes device kind {dev.device_kind!r} "
            f"({dev.platform}); known: {sorted(TARGET_BY_DEVICE_KIND)}")


def pallas_interpret() -> bool:
    """The platform rule: Pallas kernels run in interpret mode exactly when
    they execute on the CPU backend, and compile through Mosaic otherwise.
    Every kernel asks this one function."""
    return jax.default_backend() == "cpu"


def get_target(name: str) -> HardwareTarget:
    """Look up a preset by name (CLI flags)."""
    try:
        return TARGETS[name]
    except KeyError:
        raise KeyError(
            f"unknown hardware target {name!r}; presets: {sorted(TARGETS)}")
