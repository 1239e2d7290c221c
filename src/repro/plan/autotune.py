"""``repro.plan.autotune`` — the bound-guided measured autotune stage.

The blocking LP minimizes *words*; real launches also pay per-DMA issue
latency, so the words-optimal tiles are not always the fastest feasible ones.
Following the shape of arxiv 2012.15667 (use the I/O lower bound to prune the
search, then measure the survivors — viable only because, per arxiv
1802.06905, the near-bound frontier is small):

  1. **Frontier enumeration** — walk a deterministic tile neighborhood of the
     analytic plan (axis halvings/doublings, spatial divisors), clamp every
     candidate through ``fit_conv_kernel_tiles`` and keep only those that fit
     the exact halo-window VMEM budget (``conv_kernel_tiles_fit`` / the GEMM
     footprint), move words within ``policy.slack`` of the analytic optimum
     AND stay ≤ ``policy.bound_cap`` x the Thm 2.1 bound, and pass the
     ``verify.audit`` exactness check (the candidate's access plan must
     reproduce its words_fn word-for-word) — only auditable candidates are
     ever timed.
  2. **Timing** — each surviving candidate runs on-device through the
     existing ``ops.dispatch_call`` path (explicit ``plan=`` override,
     best-of-k, warmed) when an accelerator is present; otherwise the
     deterministic offline fallback prices it with the alpha-beta roofline
     ``analysis.roofline.alpha_beta_seconds`` (``hbm_seconds`` bandwidth term
     + DMA-issue latency term), under which the winner is reproducible
     bit-for-bit.
  3. **Persistence** — the winner lands in the process-wide plan cache (it
     *replaces* the analytic entry for the (op, target) pair) and in the
     versioned :class:`TuningRecord` store keyed by (op spec — shapes +
     dtypes — and target fingerprint). ``Planner.cache.save()/load()`` round-
     trips both, so production serving never re-searches: the
     ``search_count()`` counter asserts exactly that in
     ``benchmarks/autotune_bench.py``.

The analytic tiles are always in the timed set, so the tuned plan is never
slower than the analytic one under the model that ranked it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import threading
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.analysis.roofline import alpha_beta_seconds
from repro.core.conv_model import ConvShape, ceil_div
from repro.core.tiling import conv_kernel_tiles_fit, fit_conv_kernel_tiles

from . import planner as _planner
from .ops import ConvSpec, MatmulSpec, OpSpec, as_op_spec, op_from_dict
from .planner import ExecutionPlan, TunedSection, analytic_plan
from .target import HardwareTarget, TPU_V5E

# v1: {version, op, target, target_fingerprint, tiles, grid, tuned}.
TUNING_FORMAT_VERSION = 1

# words -> storage dtype of a spec stream (the inverse of the kernels'
# itemsize/4 spec precision); exotic widths are unsearchable.
_WIDTH_DTYPES = {1.0: jnp.float32, 0.5: jnp.bfloat16, 0.25: jnp.int8}


@dataclasses.dataclass(frozen=True)
class AutotunePolicy:
    """Knobs of one frontier search. Frozen/hashable so it can ride
    ``ExecutionContext(autotune=...)`` into jit-static cache keys.

    ``slack`` bounds candidate words relative to the analytic optimum (the
    frontier width); ``bound_cap`` additionally caps words against the plan's
    Thm 2.1 lower bound so no winner ever leaves the audited regime (on
    shapes where the analytic optimum itself exceeds the cap, the analytic
    words become the cap — tuning never worsens the bound ratio);
    ``max_candidates`` limits how many frontier survivors are audited+timed
    (ranked by the offline alpha-beta model first); ``timer`` picks the
    harness — ``"device"`` (best-of-``best_of``, ``warmup`` warmed calls,
    through ``ops.dispatch_call``), ``"roofline"`` (offline, deterministic),
    or ``"auto"`` (device iff a non-CPU jax backend is attached)."""

    slack: float = 1.25
    bound_cap: float = 1.3
    max_candidates: int = 16
    best_of: int = 3
    warmup: int = 1
    timer: str = "auto"  # "auto" | "device" | "roofline"

    @classmethod
    def coerce(cls, value: Any) -> Optional["AutotunePolicy"]:
        """None/False -> None (autotune off); True -> defaults; a policy
        passes through. Anything else is a caller bug."""
        if value is None or value is False:
            return None
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        raise TypeError(f"autotune policy must be None/bool/AutotunePolicy, "
                        f"got {type(value).__name__}")


@dataclasses.dataclass(frozen=True)
class TuningRecord:
    """One persisted frontier winner: the (op, target) key — op spec carries
    the shapes and dtypes, the target its fingerprint — plus the winning
    tiles/grid and the :class:`TunedSection` provenance."""

    op: OpSpec
    target: HardwareTarget
    tiles: Tuple[int, ...]
    grid: Tuple[int, ...]
    tuned: TunedSection

    @property
    def fingerprint(self) -> str:
        return target_fingerprint(self.target)

    def to_dict(self) -> Dict[str, Any]:
        return {"version": TUNING_FORMAT_VERSION,
                "op": self.op.to_dict(),
                "target": self.target.to_dict(),
                "target_fingerprint": self.fingerprint,
                "tiles": list(self.tiles),
                "grid": list(self.grid),
                "tuned": self.tuned.to_dict()}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TuningRecord":
        if d.get("version", 1) > TUNING_FORMAT_VERSION:
            raise ValueError(f"tuning record format {d['version']} is newer "
                             f"than supported {TUNING_FORMAT_VERSION}")
        target = HardwareTarget.from_dict(d["target"])
        fp = d.get("target_fingerprint")
        if fp is not None and fp != target_fingerprint(target):
            raise ValueError(
                f"tuning record fingerprint {fp} does not match its own "
                "target dict — the record was edited or the target "
                "serialization changed; re-tune instead of trusting it")
        return cls(op=op_from_dict(d["op"]), target=target,
                   tiles=tuple(int(v) for v in d["tiles"]),
                   grid=tuple(int(v) for v in d["grid"]),
                   tuned=TunedSection.from_dict(d["tuned"]))


def _normalize(op: OpSpec, target: HardwareTarget) -> OpSpec:
    """Pin ``prec=None`` (target-default precision) specs to the target's
    concrete precision: a kernel entry re-derives its spec from real dtypes
    (explicit prec), so records must key on the resolved form for both entry
    paths to share one TuningRecord."""
    if getattr(op, "prec", None) is None:
        return dataclasses.replace(op, prec=target.precision)
    return op


def target_fingerprint(target: HardwareTarget) -> str:
    """Stable 12-hex digest of the target's serialized form — the part of
    the TuningRecord key that invalidates records when the hardware model
    (VMEM size, alignment, precision policy...) changes."""
    blob = json.dumps(target.to_dict(), sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


# ---------------------------------------------------------------------------
# The TuningRecord store (process-wide, mirrored to disk by PlanCache).
# ---------------------------------------------------------------------------

_RECORDS: Dict[Tuple[OpSpec, HardwareTarget], TuningRecord] = {}
_LOCK = threading.Lock()
_SEARCHES = 0  # frontier searches actually run (cache hits don't count)


def search_count() -> int:
    """Frontier searches run so far in this process. Survives
    ``PlanCache.clear()`` on purpose: a save/clear/load round trip followed
    by re-planning must leave this unchanged (zero re-searches)."""
    return _SEARCHES


def reset_search_count() -> None:
    global _SEARCHES
    _SEARCHES = 0


def records() -> List[TuningRecord]:
    """Snapshot of every stored tuning record (insertion order)."""
    with _LOCK:
        return list(_RECORDS.values())


def clear_records() -> None:
    """Drop all tuning records and evict their materialized plans from the
    plan cache (analytic entries stay)."""
    with _LOCK:
        _RECORDS.clear()
    with _planner._CACHE_LOCK:
        for key in [k for k, p in _planner._CACHE.items()
                    if p.tuned is not None]:
            del _planner._CACHE[key]


def install_record(rec: TuningRecord) -> None:
    """Adopt a tuning record (fresh search or cache load). The stale plan-
    cache entry for its key is evicted so the winner takes over process-wide
    on the next resolve."""
    key = (rec.op, rec.target)
    with _LOCK:
        _RECORDS[key] = rec
    with _planner._CACHE_LOCK:
        _planner._CACHE.pop(key, None)


def lookup_plan(op: Union[OpSpec, ConvShape], target: HardwareTarget
                ) -> Optional[ExecutionPlan]:
    """The tuned plan for (op, target) if a record exists, else None.
    Materialization is memoized through the process-wide plan cache."""
    op = _normalize(as_op_spec(op), target)
    with _LOCK:
        rec = _RECORDS.get((op, target))
    if rec is None:
        return None
    return _materialize(rec, op, target)


def _materialize(rec: TuningRecord, op: OpSpec, target: HardwareTarget
                 ) -> ExecutionPlan:
    """Graft the record's winner onto an analytic base plan (bounds,
    blocking witness, sharding and dtypes are the base's), validate it
    through the registered plan-audit hooks, and install it as THE cached
    plan for the pair."""
    key = (op, target)
    with _planner._CACHE_LOCK:
        cached = _planner._CACHE.get(key)
    if cached is not None and cached.tuned == rec.tuned \
            and cached.tiles == rec.tiles:
        return cached
    base = cached if (cached is not None and cached.tuned is None) else None
    if base is None:
        base = (_planner._plan_conv(op, target) if isinstance(op, ConvSpec)
                else _planner._plan_matmul(op, target))
    tuned = dataclasses.replace(
        base, tiles=rec.tiles, grid=rec.grid,
        comm_volume=float(rec.tuned.winner_words),
        efficiency=float(rec.tuned.winner_words) / max(base.lower_bound, 1.0),
        tuned=rec.tuned)
    for hook in _planner._PLAN_AUDIT_HOOKS:
        hook(tuned)
    with _planner._CACHE_LOCK:
        _planner._CACHE[key] = tuned
    return tuned


# ---------------------------------------------------------------------------
# Op call derivation: OpSpec -> (op name, spec args, spec kw) for the
# registry's pallas entry — the same call shape ops.explain consumes.
# ---------------------------------------------------------------------------

def _dtype_of(width: float):
    try:
        return _WIDTH_DTYPES[float(width)]
    except KeyError:
        raise ValueError(f"no searchable dtype for stream width {width}")


def _op_call(op: OpSpec, target: HardwareTarget
             ) -> Tuple[str, tuple, Dict[str, Any]]:
    prec = op.prec or target.precision
    if isinstance(op, ConvSpec):
        H = (op.h_O - 1) * op.sh + op.h_F  # tight VALID input extent
        W = (op.w_O - 1) * op.sw + op.w_F
        xd, wd, od = (_dtype_of(prec.p_I), _dtype_of(prec.p_F),
                      _dtype_of(prec.p_O))
        xs = jax.ShapeDtypeStruct((op.N, op.c_I, H, W), xd)
        ws = jax.ShapeDtypeStruct((op.c_O, op.c_I, op.h_F, op.w_F), wd)
        kw = {"stride": (op.sh, op.sw), "out_dtype": od}
        if xd == jnp.int8:
            sc = jax.ShapeDtypeStruct((1, op.c_O), jnp.float32)
            return "conv2d_q", (xs, ws, sc), kw
        return "conv2d", (xs, ws), kw
    if isinstance(op, MatmulSpec):
        ad, bd, od = (_dtype_of(prec.p_I), _dtype_of(prec.p_F),
                      _dtype_of(prec.p_O))
        a = jax.ShapeDtypeStruct((op.m, op.k), ad)
        b = jax.ShapeDtypeStruct((op.k, op.n), bd)
        kw = {"out_dtype": od}
        if ad == jnp.int8:
            sc = jax.ShapeDtypeStruct((1, op.n), jnp.float32)
            return "matmul_q", (a, b, sc), kw
        return "matmul", (a, b), kw
    raise TypeError(f"autotune cannot search {type(op).__name__} plans "
                    "(attention tiles are closed-form)")


def supports(op: Union[OpSpec, ConvShape],
             target: HardwareTarget = TPU_V5E) -> bool:
    """True iff the frontier enumerator can search this (op, target)."""
    try:
        _op_call(as_op_spec(op), target)
        return True
    except (TypeError, ValueError):
        return False


# ---------------------------------------------------------------------------
# Frontier enumeration.
# ---------------------------------------------------------------------------

def _axis_options(v: int, cap: int, spatial: bool) -> List[int]:
    opts = {1, v // 2, v, v * 2, v * 4, cap}
    if spatial:
        # divisor-aligned spatial blocks avoid padded-launch waste entirely
        opts |= {d for d in range(1, cap + 1) if cap % d == 0}
    return sorted({min(cap, max(1, o)) for o in opts if o})


def _conv_candidates(op: ConvSpec, target: HardwareTarget,
                     base: Tuple[int, ...]) -> List[Tuple[int, ...]]:
    shape = op.to_shape(target.precision)
    mem = target.memory_model()
    align = target.align
    caps = (op.N, op.c_I, op.c_O, op.h_O, op.w_O)
    axes = [_axis_options(base[0], caps[0], False),
            _axis_options(base[1], caps[1], False),
            _axis_options(base[2], caps[2], False),
            _axis_options(base[3], caps[3], True),
            _axis_options(base[4], caps[4], True)]
    seen: Dict[Tuple[int, ...], None] = {tuple(base): None}
    for bN in axes[0]:
        for b_cI in axes[1]:
            for b_cO in axes[2]:
                for bh in axes[3]:
                    for bw in axes[4]:
                        t = fit_conv_kernel_tiles(
                            shape, (bN, b_cI, b_cO, bh, bw), mem, align)
                        if conv_kernel_tiles_fit(shape, t, mem, align):
                            seen.setdefault(tuple(t), None)
    return list(seen)


def _matmul_candidates(op: MatmulSpec, target: HardwareTarget,
                       base: Tuple[int, ...]) -> List[Tuple[int, ...]]:
    prec = op.prec or target.precision
    mem = target.memory_model()
    al = _planner.matmul_aligns(target, prec)
    caps = (op.m, op.n, op.k)

    def fp(t):
        return (t[0] * t[2] * prec.p_I + t[2] * t[1] * prec.p_F
                + t[0] * t[1] * prec.p_O)

    seen: Dict[Tuple[int, ...], None] = {tuple(base): None}
    axes = [_axis_options(base[i], caps[i], False) for i in range(3)]
    for bm in axes[0]:
        for bn in axes[1]:
            for bk in axes[2]:
                t = _planner.snap_matmul_tiles((bm, bn, bk), caps, al)
                t = _planner._fit_matmul_tiles(t, prec, mem, target)
                if fp(t) <= mem.M_eff:
                    seen.setdefault(tuple(t), None)
    return list(seen)


def _candidate_grid(op: OpSpec, t: Tuple[int, ...]) -> Tuple[int, ...]:
    if isinstance(op, ConvSpec):
        return (ceil_div(op.N, t[0]), ceil_div(op.c_O, t[2]),
                ceil_div(op.h_O, t[3]), ceil_div(op.w_O, t[4]),
                ceil_div(op.c_I, t[1]))
    return (ceil_div(op.m, t[0]), ceil_div(op.n, t[1]),
            ceil_div(op.k, t[2]))


def _transfers(grid: Tuple[int, ...]) -> int:
    """DMA issues of one launch: two streamed operand copies per grid step
    (both kernels double-buffer input+filter / A+B) plus one output store
    per outer cell (the last grid axis is the reduction)."""
    steps = math.prod(grid)
    return 2 * steps + steps // max(grid[-1], 1)


def _offline_seconds(words: float, grid: Tuple[int, ...]) -> float:
    return alpha_beta_seconds(words, _transfers(grid))


def predicted_seconds(plan: ExecutionPlan,
                      words: Optional[float] = None) -> float:
    """Offline alpha-beta wall time of one launch of ``plan`` — the same
    model the roofline timer ranks candidates with, so analytic and tuned
    plans are comparable on it. ``words`` defaults to the plan's
    ``comm_volume``; pass the measured words for the exact launch geometry
    when available (``benchmarks/autotune_bench.py`` does)."""
    w = float(plan.comm_volume if words is None else words)
    return _offline_seconds(w, plan.grid)


def _candidate_plan(base: ExecutionPlan, op: OpSpec, tiles: Tuple[int, ...],
                    words: float) -> ExecutionPlan:
    return dataclasses.replace(
        base, tiles=tuple(tiles), grid=_candidate_grid(op, tiles),
        comm_volume=float(words),
        efficiency=float(words) / max(base.lower_bound, 1.0))


# ---------------------------------------------------------------------------
# The search: enumerate -> filter (slack, bound, audit) -> time -> persist.
# ---------------------------------------------------------------------------

def _use_device_timer(policy: AutotunePolicy) -> bool:
    if policy.timer == "device":
        return True
    if policy.timer == "roofline":
        return False
    return jax.default_backend() not in ("cpu",)


def _time_device(op_name: str, ctx, spec_args: tuple, spec_kw: dict,
                 cand: ExecutionPlan, policy: AutotunePolicy) -> float:
    """Best-of-k warmed wall clock of one candidate through the real
    dispatch path (explicit plan override -> the kernel lowers exactly the
    candidate's tiles)."""
    from repro import ops as _ops

    args = tuple(jnp.zeros(a.shape, a.dtype) for a in spec_args)
    kw = dict(spec_kw)
    fn = jax.jit(lambda *xs: _ops.dispatch_call(
        op_name, ctx, str(xs[0].dtype), (), xs, spec_kw=kw, plan=cand))
    for _ in range(max(1, policy.warmup)):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(1, policy.best_of)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _search(op: OpSpec, target: HardwareTarget, policy: AutotunePolicy
            ) -> TuningRecord:
    global _SEARCHES
    _SEARCHES += 1
    from repro.ops import ExecutionContext
    from repro.ops import registry as _registry
    from repro.ops.dispatch import DispatchDecision
    from repro.verify import audit as _audit

    op_name, spec_args, spec_kw = _op_call(op, target)
    ctx = ExecutionContext(target=target, backend="pallas")
    entry = _registry.get_backend("pallas").ops[op_name]
    got = entry.spec_fn(*spec_args, **spec_kw)
    if got != op:
        raise ValueError(
            f"autotune spec round-trip failed: derived call re-specs to "
            f"{got}, not {op} — refusing to tune the wrong op")

    base = analytic_plan(op, target)
    if base.tuned is not None:  # cache already holds a winner's plan: rebuild
        base = (_planner._plan_conv(op, target) if isinstance(op, ConvSpec)
                else _planner._plan_matmul(op, target))

    def words_of(cand: ExecutionPlan) -> float:
        return float(entry.words_fn(ctx, cand, *spec_args, **spec_kw))

    tiles_list = (_conv_candidates(op, target, base.tiles)
                  if isinstance(op, ConvSpec)
                  else _matmul_candidates(op, target, base.tiles))
    base_words = words_of(base)
    # The bound cap never excludes the analytic plan itself: on shapes whose
    # irreducible halo/store overhead puts even the LP optimum above
    # bound_cap x the Thm 2.1 bound (ResNet-50 conv5_x measures 1.35x), the
    # analytic words become the cap — tuning may never *worsen* the ratio.
    cap = max(policy.bound_cap * base.lower_bound, base_words)
    frontier: List[Tuple[ExecutionPlan, float]] = []
    for t in tiles_list:
        cand = _candidate_plan(base, op, t, 0.0)
        w = words_of(cand)
        if w > policy.slack * base_words + 1e-9:
            continue
        if w > cap + 1e-9:
            continue
        frontier.append((_candidate_plan(base, op, t, w), w))
    # rank by the offline model; the analytic tiles are always kept so the
    # winner can never rank behind the plan it started from
    frontier.sort(key=lambda cw: (_offline_seconds(cw[1], cw[0].grid),
                                  cw[1], cw[0].tiles))
    keep = frontier[:max(1, policy.max_candidates)]
    if not any(c.tiles == base.tiles for c, _ in keep):
        keep.append((_candidate_plan(base, op, base.tiles, base_words),
                     base_words))

    # audit gate: only candidates whose access plan reproduces their words_fn
    # exactly (and fits VMEM, and holds the bound ratio) may be timed
    audited: List[Tuple[ExecutionPlan, float]] = []
    for cand, w in keep:
        decision = DispatchDecision(op=op_name, requested="pallas",
                                    chosen="pallas", plan=cand,
                                    measured_words=w, plan_source="explicit")
        ap = entry.access_plan_fn(ctx, cand, *spec_args, **spec_kw)
        if _audit.audit_decision(ap, decision, target=target).ok:
            audited.append((cand, w))

    device = _use_device_timer(policy)
    timed: List[Tuple[float, float, ExecutionPlan]] = []
    for cand, w in audited:
        if device:
            secs = _time_device(op_name, ctx, spec_args, spec_kw, cand,
                                policy)
        else:
            secs = _offline_seconds(w, cand.grid)
        timed.append((secs, w, cand))
    secs, w, winner = min(timed, key=lambda swc: (swc[0], swc[1],
                                                  swc[2].tiles))
    tuned = TunedSection(source="device" if device else "roofline",
                         candidates_timed=len(timed), winner_words=w,
                         winner_seconds=secs)
    return TuningRecord(op=op, target=target, tiles=winner.tiles,
                        grid=winner.grid, tuned=tuned)


def autotune(op: Union[OpSpec, ConvShape], target: HardwareTarget = TPU_V5E,
             policy: Any = None) -> ExecutionPlan:
    """Tuned plan for (op, target): reuse the stored TuningRecord, else run
    one frontier search and persist the winner (plan cache + record store).
    Raises TypeError/ValueError for unsearchable ops — guard with
    :func:`supports` when tuning opportunistically."""
    op = _normalize(as_op_spec(op), target)
    pol = AutotunePolicy.coerce(policy if policy is not None else True)
    with _LOCK:
        rec = _RECORDS.get((op, target))
    if rec is None:
        rec = _search(op, target, pol)
        install_record(rec)
    return _materialize(rec, op, target)
