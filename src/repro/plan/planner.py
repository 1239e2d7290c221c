"""The planning front door: ``Planner(target).plan(op) -> ExecutionPlan``.

One discipline for the whole codebase (paper §3.2 eq. 6, §4.2, §5): solve the
HBL-derived blocking LP against the target's memory-hierarchy model, refine to
integers, then lower the solution to (a) Pallas tile/grid shapes and (b) — for
multi-device targets — a mesh ``ShardingPlan`` with PartitionSpecs.

:class:`Planner` is the single public entry point: ``.plan(op)`` resolves
through the shared :func:`resolve_plan` path (explicit > tuned > analytic),
``.autotune(op)`` runs the measured frontier search of ``repro.plan.autotune``
and ``Planner.cache`` (a process-wide :class:`PlanCache`) saves/loads both the
memoized plans and the autotuner's :class:`~repro.plan.autotune.TuningRecord`
store. The PR-1 module-level functions (``plan``, ``save_plan_cache``,
``load_plan_cache``, ``clear_plan_cache``, ``plan_cache_size``) remain as thin
shims that emit ``DeprecationWarning``.

Plans are memoized process-wide, keyed on the (op, target) value pair; this
replaces the per-kernel ``functools.lru_cache``s the planners used to carry.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from repro.core.bounds import (attention_bound, combined_parallel_bound,
                               single_processor_bound)
from repro.core.conv_model import ConvShape, Precision, ceil_div, round_up
from repro.core.parallel_tiling import optimize_parallel_blocking
from repro.core.sharding_opt import ShardingPlan, plan_conv_sharding
from repro.core.tiling import (Blocking, attention_block_size,
                               conv_kernel_footprints, conv_kernel_geometry,
                               fit_conv_kernel_tiles, layout_align,
                               matmul_blocking, optimize_blocking)

from .ops import (AttentionSpec, ConvSpec, MatmulSpec, OpSpec, as_op_spec,
                  op_from_dict)
from .target import HardwareTarget, TPU_V5E, default_target

# v2: conv tiles/grid widened from (bN, b_cI, b_cO) / 3-axis grids to the
# spatial-blocked (bN, b_cI, b_cO, b_hO, b_wO) / 5-axis form. v1 conv dumps
# are upgraded on load (spatial kept whole, the old kernel behavior).
# v3: multi-device conv plans carry a ``parallel`` section (the integer
# processor grid the parallel LP chose plus the predicted per-processor
# words and the Thm 2.2/2.3 bound). v2 dumps load with parallel=None.
# v4: attention plans (kind="attention", closed-form (bq, bk) tiles, bound
# from core.bounds.attention_bound, empty blocking). Older dumps load as-is.
# v5: plans carry the per-operand storage dtype map (``dtypes``) derived
# from the op's word-widths — quantized ops record int8 streams / bf16
# stores so tools (roofline byte conversion, bench dumps) need not guess.
# Older dumps load with dtypes=().
# v6: plans may carry a ``tuned`` section ({source, candidates_timed,
# winner_words, winner_seconds}) stamped by the measured autotuner
# (``repro.plan.autotune``) — absent (None) on analytic plans and in every
# older dump.
PLAN_FORMAT_VERSION = 6


def _width_dtype(width: float) -> str:
    """Storage-dtype name of a word width (int8 canonicalizes the 0.25-word
    class; fractional widths such as a quantized KV stream's p_F = 0.25 +
    1/hd keep their numeric form)."""
    names = {1.0: "float32", 0.5: "bfloat16", 0.25: "int8"}
    return names.get(float(width), f"words:{float(width):g}")


def _plan_dtypes(prec: Precision) -> Tuple[Tuple[str, str], ...]:
    """The per-operand dtype map a v5 plan carries. Accumulation is always
    f32 (every kernel's discipline, VRF013)."""
    return (("input", _width_dtype(prec.p_I)),
            ("filter", _width_dtype(prec.p_F)),
            ("output", _width_dtype(prec.p_O)),
            ("accum", "float32"))


@dataclasses.dataclass(frozen=True)
class ParallelSection:
    """The distributed leg of a multi-device conv plan (paper §4.2).

    ``grid`` is the integer processor grid the parallel LP chose (sorted
    (axis, procs) pairs over the distributable axes), ``comm_words`` the
    blocking model's predicted per-processor network words, and
    ``lower_bound`` the combined Thm 2.2/2.3 per-processor bound at the
    target's effective local capacity. ``repro.distributed`` lowers exactly
    this grid onto a mesh; ``DispatchDecision.bound_ratio`` for the
    ``conv2d_dist`` op divides measured inter-device words by this bound."""

    grid: Tuple[Tuple[str, int], ...]  # sorted (axis, procs), procs > 1 only
    P: int
    comm_words: float
    lower_bound: float

    @property
    def grid_dict(self) -> Dict[str, int]:
        return dict(self.grid)

    def to_dict(self) -> Dict[str, Any]:
        return {"grid": [list(kv) for kv in self.grid], "P": self.P,
                "comm_words": self.comm_words,
                "lower_bound": self.lower_bound}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ParallelSection":
        return cls(grid=tuple((str(k), int(v)) for k, v in d["grid"]),
                   P=int(d["P"]), comm_words=float(d["comm_words"]),
                   lower_bound=float(d["lower_bound"]))


@dataclasses.dataclass(frozen=True)
class TunedSection:
    """The measured-autotune provenance a v6 plan carries (None = analytic).

    ``source`` records how the winner was timed — ``"device"`` (best-of-k
    wall clock through ``ops.dispatch_call``) or ``"roofline"`` (the offline
    alpha-beta model ``analysis.roofline.alpha_beta_seconds``); ``winner_words``
    is the winner's exact measured HBM words (== the plan's ``comm_volume``)
    and ``winner_seconds`` its timed/modeled launch seconds."""

    source: str  # "device" | "roofline"
    candidates_timed: int
    winner_words: float
    winner_seconds: float

    def to_dict(self) -> Dict[str, Any]:
        return {"source": self.source,
                "candidates_timed": self.candidates_timed,
                "winner_words": self.winner_words,
                "winner_seconds": self.winner_seconds}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TunedSection":
        return cls(source=str(d["source"]),
                   candidates_timed=int(d["candidates_timed"]),
                   winner_words=float(d["winner_words"]),
                   winner_seconds=float(d["winner_seconds"]))


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything a consumer needs to execute one op on one target.

    ``tiles`` is the kernel-facing tuple — (bN, b_cI, b_cO, b_hO, b_wO) for
    conv (spatial blocks included: the kernel loads overlapping halo windows
    of (b_hO - 1) * sh + h_F input rows), (bm, bn, bk) for matmul — and
    ``blocking`` the full 9-axis integer LP solution it was collapsed from.
    ``grid`` is the Pallas launch grid over the padded problem. ``sharding``
    is present iff the target has mesh axes; conv plans for such targets
    additionally carry ``parallel`` — the §4.2 processor grid + predicted
    per-processor words that ``repro.distributed`` executes.
    """

    op: OpSpec
    target: HardwareTarget
    blocking: Tuple[Tuple[str, int], ...]  # sorted (axis, block) pairs
    tiles: Tuple[int, ...]
    grid: Tuple[int, ...]
    comm_volume: float  # modeled slow<->fast words moved
    lower_bound: float  # Thm 2.1 bound at the target's effective capacity
    efficiency: float  # comm_volume / lower_bound
    sharding: Optional[ShardingPlan] = None
    parallel: Optional[ParallelSection] = None
    # v5: per-operand storage dtypes ((operand, dtype) pairs — input/filter/
    # output/accum), derived from the op's effective Precision. () in
    # pre-v5 dumps.
    dtypes: Tuple[Tuple[str, str], ...] = ()
    # v6: measured-autotune provenance; None on analytic plans and in every
    # pre-v6 dump. A tuned plan's tiles/grid/comm_volume are the frontier
    # winner's, so consumers need not special-case it.
    tuned: Optional[TunedSection] = None

    # -- views ---------------------------------------------------------------
    @property
    def blocking_dict(self) -> Dict[str, int]:
        return dict(self.blocking)

    @property
    def precision(self) -> Precision:
        return self.op.prec or self.target.precision

    def to_shape(self) -> ConvShape:
        return self.op.to_shape(self.target.precision)

    def as_blocking(self) -> Blocking:
        return Blocking(self.blocking_dict, self.to_shape())

    def conv_tiles(self) -> Tuple[int, int, int, int, int]:
        if not isinstance(self.op, ConvSpec):
            raise TypeError("conv_tiles() on a non-conv plan")
        return self.tiles  # (bN, b_cI, b_cO, b_hO, b_wO)

    def matmul_tiles(self) -> Tuple[int, int, int]:
        if not isinstance(self.op, MatmulSpec):
            raise TypeError("matmul_tiles() on a non-matmul plan")
        return self.tiles  # (bm, bn, bk)

    def conv_tile(self) -> Dict[str, int]:
        """The collapsed per-axis conv tile (as_conv_tile view)."""
        return self.as_blocking().as_conv_tile()

    def footprints(self) -> Dict[str, float]:
        """Words each array block occupies in fast memory (split-buffer
        accounting: input+filter -> scratchpad, output -> accumulator)."""
        blk = self.as_blocking()
        return {"input": blk.in_block_words, "filter": blk.filt_block_words,
                "output": blk.out_block_words}

    def kernel_footprints(self) -> Dict[str, float]:
        """Words the lowered conv2d kernel actually allocates per tile: the
        halo window of the space-to-depth input and the full unrolled filter
        block, lane/sublane padding included — the view
        ``fit_conv_kernel_tiles`` clamped the tiles against."""
        if not isinstance(self.op, ConvSpec):
            raise TypeError("kernel_footprints() on a non-conv plan")
        return conv_kernel_footprints(self.to_shape(), self.tiles,
                                      align=self.target.align)

    def pallas_specs(self):
        """(grid, in_specs, out_specs) mirroring what the kernels lower.
        Lazy pallas import so plan inspection works without a jax runtime.

        Both kernels keep their inputs in ANY/HBM memory and stream
        double-buffered DMA windows into VMEM scratch themselves (the conv
        input needs overlapping halo windows, which no blocked BlockSpec can
        express), so the in_specs carry only the memory space; the output
        spec is blocked as before."""
        from jax.experimental import pallas as pl

        if isinstance(self.op, AttentionSpec):
            raise TypeError("pallas_specs() on an attention plan: the flash "
                            "kernels own their BlockSpecs (tiles = (bq, bk))")
        in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)]
        if isinstance(self.op, MatmulSpec):
            bm, bn, bk = self.tiles
            return (self.grid, in_specs,
                    pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        bN, b_cI, b_cO, b_hO, b_wO = self.tiles
        return (self.grid, in_specs,
                pl.BlockSpec((b_hO, b_wO, bN, b_cO),
                             lambda n, co, h, w, ci: (h, w, n, co)))

    # -- (de)serialization ----------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "version": PLAN_FORMAT_VERSION,
            "op": self.op.to_dict(),
            "target": self.target.to_dict(),
            "blocking": [list(kv) for kv in self.blocking],
            "tiles": list(self.tiles),
            "grid": list(self.grid),
            "comm_volume": self.comm_volume,
            "lower_bound": self.lower_bound,
            "efficiency": self.efficiency,
            "sharding": None,
            "parallel": (None if self.parallel is None
                         else self.parallel.to_dict()),
            "dtypes": [list(kv) for kv in self.dtypes],
            "tuned": None if self.tuned is None else self.tuned.to_dict(),
        }
        if self.sharding is not None:
            s = self.sharding
            d["sharding"] = {
                "binding": dict(s.binding),
                "mesh_axes": [list(ax) for ax in s.mesh_axes],
                "comm_per_processor": s.comm_per_processor,
                "grid": dict(s.grid),
            }
        return d

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ExecutionPlan":
        if d.get("version", 1) > PLAN_FORMAT_VERSION:
            raise ValueError(f"plan format {d['version']} is newer than "
                             f"supported {PLAN_FORMAT_VERSION}")
        if d.get("version", 1) < 2 and d["op"].get("kind") == "conv":
            # v1 conv plans: 3-tuple tiles, (nN, n_cO, n_cI) grid. Upgrade to
            # the spatial-blocked form with spatial kept whole.
            op = d["op"]
            d = dict(d, tiles=list(d["tiles"]) + [op["h_O"], op["w_O"]],
                     grid=[d["grid"][0], d["grid"][1], 1, 1, d["grid"][2]])
        sharding = None
        if d.get("sharding") is not None:
            s = d["sharding"]
            sharding = ShardingPlan(
                binding=dict(s["binding"]),
                mesh_axes=tuple((str(n), int(sz)) for n, sz in s["mesh_axes"]),
                comm_per_processor=float(s["comm_per_processor"]),
                grid={k: int(v) for k, v in s["grid"].items()},
            )
        parallel = None
        if d.get("parallel") is not None:  # absent in v1/v2 dumps
            parallel = ParallelSection.from_dict(d["parallel"])
        tuned = None
        if d.get("tuned") is not None:  # absent in pre-v6 dumps
            tuned = TunedSection.from_dict(d["tuned"])
        return cls(
            op=op_from_dict(d["op"]),
            target=HardwareTarget.from_dict(d["target"]),
            blocking=tuple((str(k), int(v)) for k, v in d["blocking"]),
            tiles=tuple(int(v) for v in d["tiles"]),
            grid=tuple(int(v) for v in d["grid"]),
            comm_volume=float(d["comm_volume"]),
            lower_bound=float(d["lower_bound"]),
            efficiency=float(d["efficiency"]),
            sharding=sharding,
            parallel=parallel,
            dtypes=tuple((str(k), str(v))
                         for k, v in d.get("dtypes", [])),
            tuned=tuned,
        )

    @classmethod
    def from_json(cls, s: str) -> "ExecutionPlan":
        return cls.from_dict(json.loads(s))


# ---------------------------------------------------------------------------
# The process-wide plan cache (one memoizer for every consumer).
# ---------------------------------------------------------------------------

_CACHE: Dict[Tuple[OpSpec, HardwareTarget], ExecutionPlan] = {}
_CACHE_LOCK = threading.Lock()
# Bounded like the per-kernel lru_caches it replaces (256 + 512): long-running
# servers planning many distinct shapes must not grow memory without limit.
PLAN_CACHE_MAX = 1024


class PlanCache:
    """Facade over the process-wide plan memoizer *and* the autotuner's
    TuningRecord store — one save/load/clear/size surface, reached as
    ``Planner.cache`` (a process singleton: every instance views the same
    state). The JSON dump is a ``{"format", "plans", "tuning"}`` dict;
    pre-v6 dumps (a bare list of plan dicts) still load."""

    def size(self) -> int:
        """Number of memoized plans (analytic and materialized tuned)."""
        with _CACHE_LOCK:
            return len(_CACHE)

    def clear(self) -> None:
        """Drop every memoized plan and every tuning record. The autotune
        search counter is *not* reset — re-searches stay observable across
        a clear()/load() round trip."""
        with _CACHE_LOCK:
            _CACHE.clear()
        from . import autotune as _autotune

        _autotune.clear_records()

    def save(self, path: str) -> int:
        """Dump memoized plans + tuning records; returns entries written."""
        from . import autotune as _autotune

        with _CACHE_LOCK:
            plans = list(_CACHE.values())
        records = _autotune.records()
        with open(path, "w") as f:
            json.dump({"format": PLAN_FORMAT_VERSION,
                       "plans": [p.to_dict() for p in plans],
                       "tuning": [r.to_dict() for r in records]}, f, indent=1)
        return len(plans) + len(records)

    def load(self, path: str) -> int:
        """Pre-populate plans + tuning records from a dump; returns entries
        loaded. Restored tuning records make ``resolve_plan`` serve tuned
        plans without re-searching (the zero-re-search serving contract)."""
        with open(path) as f:
            dump = json.load(f)
        plan_dicts = dump if isinstance(dump, list) else dump.get("plans", [])
        n = 0
        with _CACHE_LOCK:
            for d in plan_dicts:
                p = ExecutionPlan.from_dict(d)
                _CACHE.setdefault((p.op, p.target), p)
                n += 1
        if isinstance(dump, dict):
            from . import autotune as _autotune

            for d in dump.get("tuning", []):
                _autotune.install_record(_autotune.TuningRecord.from_dict(d))
                n += 1
        return n


def _warn_legacy(old: str, new: str) -> None:
    warnings.warn(f"legacy planning API: {old} is deprecated; use {new}",
                  DeprecationWarning, stacklevel=3)


def clear_plan_cache() -> None:
    _warn_legacy("clear_plan_cache()", "Planner.cache.clear()")
    Planner.cache.clear()


def plan_cache_size() -> int:
    _warn_legacy("plan_cache_size()", "Planner.cache.size()")
    return Planner.cache.size()


def save_plan_cache(path: str) -> int:
    _warn_legacy("save_plan_cache()", "Planner.cache.save()")
    return Planner.cache.save(path)


def load_plan_cache(path: str) -> int:
    _warn_legacy("load_plan_cache()", "Planner.cache.load()")
    return Planner.cache.load(path)


# ---------------------------------------------------------------------------
# Lowering: OpSpec x HardwareTarget -> ExecutionPlan.
# ---------------------------------------------------------------------------

def _conv_align(shape: ConvShape, target: HardwareTarget) -> Optional[Dict[str, int]]:
    align: Dict[str, int] = {}
    if target.align_lane > 1:
        align["cO"] = min(target.align_lane, shape.c_O)
    if target.align_sublane > 1:
        align["cI"] = min(target.align_sublane, shape.c_I)
    return align or None


def _plan_conv(op: ConvSpec, target: HardwareTarget) -> ExecutionPlan:
    shape = op.to_shape(target.precision)
    mem = target.memory_model()
    blk = optimize_blocking(shape, mem, align=_conv_align(shape, target))
    t = blk.as_conv_tile()
    # Kernel tiles carry the LP's spatial choice: the kernel blocks h_O/w_O
    # with overlapping input halos. The lifted LP footprint can undercount
    # the kernel's (it may block filter taps the kernel unrolls in full, and
    # it prices no lane/sublane padding), so snap the tiles to the kernel's
    # layout and clamp them against its exact halo-window model.
    tiles = fit_conv_kernel_tiles(shape, (
        t["N"], t["cI"], t["cO"], t["hO"], t["wO"]), mem,
        align=target.align)
    grid = conv_kernel_geometry(shape, tiles, target.align).grid
    vol = blk.comm_volume()
    lb = single_processor_bound(shape, mem.M_eff).value
    sharding = None
    parallel = None
    if target.mesh_axes:
        sharding = plan_conv_sharding(shape, target.mesh_axes)
        parallel = _parallel_section(shape, target.n_devices, mem.M_eff)
    return ExecutionPlan(
        op=op, target=target, blocking=tuple(sorted(blk.b.items())),
        tiles=tiles, grid=grid, comm_volume=vol, lower_bound=lb,
        efficiency=vol / max(lb, 1.0), sharding=sharding, parallel=parallel,
        dtypes=_plan_dtypes(op.prec or target.precision))


def _parallel_section(shape: ConvShape, P: int, M_eff: float
                      ) -> ParallelSection:
    """The §4.2 leg of a multi-device conv plan: the parallel LP's integer
    grid restricted to the axes ``repro.distributed`` can lower, its modeled
    per-processor words, and the combined Thm 2.2/2.3 bound."""
    # local import keeps repro.plan importable without the distributed pkg
    from repro.distributed.geometry import DIST_AXES

    pb = optimize_parallel_blocking(shape, P, restrict_axes=DIST_AXES)
    return ParallelSection(
        grid=tuple(sorted((k, v) for k, v in pb.grid.items() if v > 1)),
        P=pb.P,
        comm_words=pb.comm_per_processor(),
        lower_bound=combined_parallel_bound(shape, P, M_eff))


def matmul_aligns(target: HardwareTarget, prec) -> Tuple[int, int, int]:
    """(bm, bn, bk) multiples: m rides the sublanes of A (packed dtypes
    stack more rows per sublane), n and k ride lanes."""
    sub, lane = layout_align(target.align, prec.p_I)
    return sub, lane, lane


def snap_matmul_tiles(tiles: Tuple[int, int, int], dims: Tuple[int, int, int],
                      aligns: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Round each tile down to its alignment multiple (at least one) and cap
    it at the dim rounded up to that multiple. A dim below one aligned tile
    gets a whole aligned tile: the kernel zero-pads it, because Mosaic
    slices HBM only in whole (sublane, lane) tiles."""
    out = []
    for v, dim, al in zip(tiles, dims, aligns):
        if al <= 1:
            out.append(max(1, min(v, dim)))
        else:
            out.append(min(max(al, (v // al) * al), round_up(dim, al)))
    return out[0], out[1], out[2]


def _fit_matmul_tiles(tiles: Tuple[int, int, int], prec, mem,
                      target: HardwareTarget) -> Tuple[int, int, int]:
    """Shrink snapped (bm, bn, bk) until the GEMM tile footprint
    ``bm*bk*p_I + bk*bn*p_F + bm*bn*p_O`` fits the double-buffered budget
    ``mem.M_eff`` (the constraint ``optimize_blocking`` solved under, which
    alignment snapping can violate). Alignment floors are respected."""
    bm, bn, bk = tiles
    aligns = matmul_aligns(target, prec)

    def fp(t):
        return (t[0] * t[2] * prec.p_I + t[2] * t[1] * prec.p_F
                + t[0] * t[1] * prec.p_O)

    def shrink(v, al):
        nv = (v // 2 // al) * al if v // 2 >= al else min(v, al)
        return max(nv, 1)

    b = [bm, bn, bk]
    while fp(b) > mem.M_eff:
        best_i, best_gain = None, 0.0
        for i, al in enumerate(aligns):
            nv = shrink(b[i], al)
            if nv >= b[i]:
                continue
            trial = list(b)
            trial[i] = nv
            gain = fp(b) - fp(trial)
            if gain > best_gain:
                best_i, best_gain = i, gain
        if best_i is None:
            break  # nothing left to shrink; keep the least-bad tiles
        b[best_i] = shrink(b[best_i], aligns[best_i])
    return b[0], b[1], b[2]


def _plan_matmul(op: MatmulSpec, target: HardwareTarget) -> ExecutionPlan:
    prec = op.prec or target.precision
    mem = target.memory_model()
    blk = matmul_blocking(op.m, op.n, op.k, mem=mem, prec=prec,
                          align_m=target.align_sublane,
                          align_n=target.align_lane,
                          align_k=target.align_lane)
    bm, bn, bk = snap_matmul_tiles(
        (blk.b["N"], blk.b["cO"], blk.b["cI"]), (op.m, op.n, op.k),
        matmul_aligns(target, prec))
    # MXU alignment can inflate a tile past the LP's feasible point (e.g. the
    # lane snap turns b_k = 1 into 128 on tall-skinny im2col GEMMs), silently
    # breaking the double-buffered capacity discipline the kernel allocates
    # under — caught by the repro.verify static auditor. Re-fit like
    # fit_conv_kernel_tiles: halve the best-gain axis (alignment floors kept)
    # until the A + B + accumulator footprint obeys M_eff again.
    bm, bn, bk = _fit_matmul_tiles((bm, bn, bk), prec, mem, target)
    tiles = (bm, bn, bk)
    grid = (ceil_div(op.m, bm), ceil_div(op.n, bn), ceil_div(op.k, bk))
    shape = op.to_shape(target.precision)
    vol = blk.comm_volume()
    lb = single_processor_bound(shape, mem.M_eff).value
    sharding = None
    if target.mesh_axes:
        sharding = plan_conv_sharding(shape, target.mesh_axes,
                                      shardable=("N", "cI", "cO"))
    return ExecutionPlan(
        op=op, target=target, blocking=tuple(sorted(blk.b.items())),
        tiles=tiles, grid=grid, comm_volume=vol, lower_bound=lb,
        efficiency=vol / max(lb, 1.0), sharding=sharding,
        dtypes=_plan_dtypes(prec))


def _plan_attention(op: AttentionSpec, target: HardwareTarget) -> ExecutionPlan:
    """Closed-form attention plan: the flash schedule's (bq, bk) capacity
    argument (``core.tiling.attention_block_size``) instead of the conv LP,
    bounded by Thm 2.1 applied to attention's two GEMMs
    (``core.bounds.attention_bound``). GQA group folding is accounted here:
    each of the B*KV kernel batch rows carries g = H/KV stacked query groups,
    so k/v stream once per folded q tile — exactly the launch geometry
    ``kernels.flash_attention`` lowers."""
    prec = op.prec or target.precision
    mem = target.memory_model()
    blk = attention_block_size(op.hd, mem.M_eff, p_kv=prec.p_F)
    g = max(1, op.H // max(op.KV, 1))
    lqf = g * op.Lq  # the folded query axis of one (batch, kv-head) row
    sub = max(target.align_sublane, 1)
    bq = min(blk, round_up(lqf, sub))
    bk = min(blk, round_up(op.Lk, sub))
    n_q, n_k = ceil_div(lqf, bq), ceil_div(op.Lk, bk)
    rows = op.B * op.KV
    vol = (prec.p_I * rows * n_q * bq * op.hd          # q tiles, loaded once
           + 2.0 * prec.p_F * rows * n_q * n_k * bk * op.hd  # k/v per q tile
           + prec.p_O * rows * n_q * bq * op.hd)       # output stores
    lb = attention_bound(op.B, op.H, op.KV, op.Lq, op.Lk, op.hd,
                         mem.M_eff, prec).value
    return ExecutionPlan(
        op=op, target=target, blocking=(), tiles=(bq, bk),
        grid=(rows, n_q, n_k), comm_volume=vol, lower_bound=lb,
        efficiency=vol / max(lb, 1.0), dtypes=_plan_dtypes(prec))


def warn_legacy_kernel_kwargs(fn: str, **passed) -> None:
    """Emit the one-PR deprecation warning for retired kernel kwargs
    (``target=``/``tiles=``): execution policy now rides a single
    ``ctx: ExecutionContext``. (``plan=`` stays — it is the dispatcher's and
    the autotuner's explicit-plan handoff.) Lint VRF015 flags new in-repo
    uses of the legacy kwargs."""
    names = [k for k, v in sorted(passed.items()) if v is not None]
    if names:
        warnings.warn(
            f"legacy kernel kwargs {names} on {fn}(): pass "
            "ctx=ExecutionContext(target=...) instead",
            DeprecationWarning, stacklevel=3)


def resolve_kernel_plan(
    op: OpSpec,
    plan: Optional[ExecutionPlan] = None,
    target: Optional[HardwareTarget] = None,
    tiles: Optional[Tuple[int, ...]] = None,
    ctx: Optional[Any] = None,
) -> Tuple[Tuple[int, ...], HardwareTarget]:
    """Shared kernel-side resolution of (tiles, target).

    ``op`` is the spec the kernel built from its actual arrays (precision
    included). Priority: explicit legacy ``tiles``, then a caller-supplied
    ``plan`` (validated for geometry and precision), then a fresh plan via
    :func:`resolve_plan` — for ``ctx.target`` (autotune-aware, any object
    with ``target``/``autotune`` attributes; duck-typed so the kernel layer
    needs no ``repro.ops`` import), legacy ``target``, or the attached
    device's :func:`default_target`. The returned target is the one the
    tiles were planned for: it sets the kernel's layout alignment and its
    compiler VMEM limit. One implementation so conv2d/matmul/... cannot
    diverge."""
    if ctx is not None and target is None:
        target = ctx.target
    if tiles is None and plan is None:
        plan, _ = resolve_plan(op, target or default_target(),
                               autotune=getattr(ctx, "autotune", None))
    if plan is not None:
        if not isinstance(plan.op, type(op)) or (
                dataclasses.replace(plan.op, prec=None)
                != dataclasses.replace(op, prec=None)):
            raise ValueError(f"plan was made for {plan.op}, not {op}")
        data_p = (op.prec or plan.target.precision).p_I
        if plan.precision.p_I < data_p:
            raise ValueError(
                f"plan assumed {plan.precision.p_I}-word input streams but "
                f"the data is {data_p} words: its tiles would overflow the "
                "modeled fast-memory budget")
        if tiles is None:
            return plan.tiles, plan.target
        target = target or plan.target
    return tiles, target or default_target()


_PLAN_AUDIT_HOOKS: List[Callable[[ExecutionPlan], None]] = []


def register_plan_audit_hook(fn: Callable[[ExecutionPlan], None]) -> None:
    """Register ``fn`` to be called on every freshly built ExecutionPlan
    (cache hits skip it — the cached object already passed). Hooks raise to
    reject a plan; ``repro.verify.audit.install_plan_audit`` uses this to
    run the static plan validator at construction time. Idempotent."""
    if fn not in _PLAN_AUDIT_HOOKS:
        _PLAN_AUDIT_HOOKS.append(fn)


def _memoize_plan(key: Tuple[OpSpec, HardwareTarget], built: ExecutionPlan
                  ) -> ExecutionPlan:
    with _CACHE_LOCK:
        while len(_CACHE) >= PLAN_CACHE_MAX:
            _CACHE.pop(next(iter(_CACHE)))  # FIFO eviction of the oldest plan
        # first writer wins so concurrent planners still converge on one object
        return _CACHE.setdefault(key, built)


def analytic_plan(op: Union[OpSpec, ConvShape],
                  target: HardwareTarget = TPU_V5E) -> ExecutionPlan:
    """Solve the blocking LP for one (op, target) pair. Memoized: repeated
    calls with an equal pair return the identical ExecutionPlan object. A
    tuned plan previously memoized for the pair (its ``tuned`` section set)
    is returned as-is — the cache holds one winner per key."""
    op = as_op_spec(op)
    key = (op, target)
    with _CACHE_LOCK:
        cached = _CACHE.get(key)
    if cached is not None:
        return cached
    if isinstance(op, ConvSpec):
        built = _plan_conv(op, target)
    elif isinstance(op, AttentionSpec):
        built = _plan_attention(op, target)
    else:
        built = _plan_matmul(op, target)
    for hook in _PLAN_AUDIT_HOOKS:
        hook(built)
    return _memoize_plan(key, built)


def resolve_plan(
    op: Union[OpSpec, ConvShape],
    target: HardwareTarget = TPU_V5E,
    explicit: Optional[ExecutionPlan] = None,
    autotune: Optional[Any] = None,
) -> Tuple[ExecutionPlan, str]:
    """THE shared plan-resolution path — ``ctx.plan()``, ``ops.explain``,
    ``resolve_kernel_plan`` and :class:`Planner` all funnel through here, so
    an explicitly-passed plan, a cached tuned plan, and a fresh analytic plan
    are distinguishable everywhere. Returns ``(plan, source)`` with source in
    ``"explicit"`` (caller-supplied, returned untouched) > ``"tuned"`` (a
    TuningRecord exists for the pair — or ``autotune`` is a truthy
    :class:`~repro.plan.autotune.AutotunePolicy` / ``True`` and the op is
    searchable, running the frontier search once) > ``"analytic"``."""
    if explicit is not None:
        return explicit, "explicit"
    op = as_op_spec(op)
    from . import autotune as _autotune

    tuned = _autotune.lookup_plan(op, target)
    if tuned is not None:
        return tuned, "tuned"
    policy = _autotune.AutotunePolicy.coerce(autotune)
    if policy is not None and _autotune.supports(op, target):
        return _autotune.autotune(op, target, policy=policy), "tuned"
    return analytic_plan(op, target), "analytic"


def plan(op: Union[OpSpec, ConvShape], target: HardwareTarget = TPU_V5E
         ) -> ExecutionPlan:
    """Deprecated module-level entry point (use ``Planner(target).plan(op)``):
    resolves through :func:`resolve_plan`, so a tuned plan cached for the
    pair is returned over the analytic one."""
    _warn_legacy("plan()", "Planner(target).plan(op)")
    return resolve_plan(op, target)[0]


class Planner:
    """The one public planning front door.

    ``Planner(target, quant=None, autotune=None)``:

      * ``quant``    - optional quantized storage policy; a non-None spec is
                       attached via ``target.with_quant`` so every plan prices
                       the quantized stream widths;
      * ``autotune`` - ``None`` (analytic only), ``True`` (default
                       :class:`~repro.plan.autotune.AutotunePolicy`), or a
                       policy instance: ``.plan()`` then runs the measured
                       frontier search on first sight of a searchable op and
                       serves the tuned winner from the TuningRecord store
                       afterwards.

    ``.plan(op)`` resolves (tuned > analytic); ``.resolve(op, explicit=...)``
    additionally reports the plan source; ``.autotune(op)`` forces a search.
    ``Planner.cache`` is the process-wide :class:`PlanCache` (save/load/
    clear/size), shared by every instance."""

    cache: PlanCache = PlanCache()

    def __init__(self, target: HardwareTarget = TPU_V5E, quant: Any = None,
                 autotune: Any = None):
        if quant is not None:
            target = target.with_quant(quant)
        self.target = target
        from . import autotune as _autotune

        self.autotune_policy = _autotune.AutotunePolicy.coerce(autotune)

    def plan(self, op: Union[OpSpec, ConvShape]) -> ExecutionPlan:
        return self.resolve(op)[0]

    def resolve(self, op: Union[OpSpec, ConvShape],
                explicit: Optional[ExecutionPlan] = None
                ) -> Tuple[ExecutionPlan, str]:
        return resolve_plan(op, self.target, explicit=explicit,
                            autotune=self.autotune_policy)

    def autotune(self, op: Union[OpSpec, ConvShape],
                 policy: Any = None) -> ExecutionPlan:
        """Run (or reuse) the measured frontier search for ``op`` and return
        the tuned plan. Raises TypeError for ops the frontier enumerator
        cannot search (attention plans are closed-form)."""
        from . import autotune as _autotune

        pol = _autotune.AutotunePolicy.coerce(
            policy if policy is not None
            else (self.autotune_policy or True))
        return _autotune.autotune(op, self.target, policy=pol)
