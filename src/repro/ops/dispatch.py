"""The dispatcher: resolve each op call to (ExecutionPlan, backend kernel).

``resolve(op, ctx, ...)`` walks the requested backend's fallback chain until
an entry's declared capabilities cover the call's required features, solves
the LP plan for entries that declare a ``spec_fn`` (through the context's
process-wide plan cache), and returns a :class:`DispatchDecision` — the
explain/trace record tests and tools assert against.

The public op wrappers (``matmul``/``conv2d``/``conv1d_causal``/``attention``)
derive the required features from the call itself (is ``q_offset`` a static
int, a traced scalar, or a per-row vector? is there a key mask?) so callers
never re-implement the capability logic. Dispatch happens at trace time:
inside ``jax.jit`` the decision is made once per compiled variant.

Observability:

  * ``explain(op, ctx, ...)``    - the decision, without executing anything;
  * ``record_dispatch()``        - context manager capturing every decision
                                   made while it is active (including those
                                   made while tracing a jit).

Runtime-failure fallback (``repro.resilience``): capability resolution only
proves an entry *claims* to serve the call. When the chosen entry actually
raises a ``TransientFault`` at execution, ``dispatch_call`` quarantines the
failing ``(op, backend, shape-key)``, re-resolves down the chain (an entry
may name an instrumented ``degrade_to`` backend so the demotion stays
priced), and retries — the resulting decision records ``degraded=True`` +
the fault name, with ``measured_words``/``bound_ratio`` re-priced for the
backend that actually served the call. A quarantined combination is probed
again after ``QUARANTINE_PROBE_AFTER`` dispatches. ``FatalFault`` always
propagates. The ``REPRO_FAULTS`` env knob installs a seeded
``resilience.FaultCampaign`` around every eager dispatch.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.resilience.errors import TransientFault

from .context import ExecutionContext, default_context
from .registry import OpEntry, get_backend

MAX_FALLBACK_DEPTH = 4  # registry misconfiguration guard, not a real limit
# runtime-fallback executor: per-call bound on demote/retry attempts
MAX_RUNTIME_ATTEMPTS = 4
# a quarantined (op, backend, shape-key) is probed again on the Nth dispatch
QUARANTINE_PROBE_AFTER = 8


@dataclasses.dataclass(frozen=True)
class DispatchDecision:
    """Why one call ran where it did — and what it moved.

    ``requested`` is the backend the context resolved to; ``chosen`` the one
    that actually served the call; ``missing`` the capabilities whose absence
    forced each fallback hop (empty when ``chosen == requested``); ``plan``
    the ExecutionPlan the chosen entry consumed (None for closed-form ops and
    for XLA entries, which delegate tiling to the compiler);
    ``plan_source`` how that plan was obtained through the shared resolution
    path (``repro.plan.resolve_plan``): ``"explicit"`` (caller passed one),
    ``"tuned"`` (the measured autotuner's stored winner), or ``"analytic"``
    (the LP optimum — also the value when the entry consumes no plan);
    ``measured_words`` the words the chosen kernel's launch geometry moves
    for this call (None when the entry is not instrumented) — HBM words
    (32-bit) for single-device ops, *inter-device* words per device for the
    distributed ops — reported via ``bound_ratio`` against the matching
    bound: the plan's Thm 2.1 ``lower_bound``, or the plan's ``parallel``
    section's Thm 2.2/2.3 bound for ``*_dist`` ops.

    ``audited`` is the static auditor's independent word count (set only
    when dispatch ran with ``audit=True``): ``repro.verify`` abstractly
    interprets the entry's :class:`KernelAccessPlan` (grid walk over the
    BlockSpec index maps + manual-DMA halo windows) and raises
    ``verify.AuditError`` unless it reproduces ``measured_words`` exactly,
    fits VMEM, stays at/below the recorded bound ratio, and the DMA
    schedule is hazard-free — so when this field is set it *equals*
    ``measured_words``.

    ``degraded``/``fault`` record *runtime* demotion: a backend along the
    chain is quarantined after actually raising the named ``TransientFault``
    (``"KernelLaunchError"``, ``"NumericFault"``, ...), so the call was
    served further down the chain than capabilities alone required —
    ``measured_words``/``bound_ratio`` are re-priced for the backend that
    ran, making the communication cost of degradation visible in
    ``ops.explain``."""

    op: str
    requested: str
    chosen: str
    missing: Tuple[str, ...] = ()
    plan: Optional[Any] = None
    plan_source: str = "analytic"  # "explicit" | "tuned" | "analytic"
    measured_words: Optional[float] = None
    audited: Optional[float] = None
    degraded: bool = False
    fault: Optional[str] = None

    @property
    def fell_back(self) -> bool:
        return self.chosen != self.requested

    @property
    def lower_bound(self) -> Optional[float]:
        """The bound ``measured_words`` is compared against: Thm 2.2/2.3
        (per-processor) for distributed ops, Thm 2.1 otherwise."""
        if self.plan is None:
            return None
        if self.op.endswith("_dist"):
            if self.plan.parallel is None:
                return None  # planned for a single-device target
            return self.plan.parallel.lower_bound
        return self.plan.lower_bound

    @property
    def bound_ratio(self) -> Optional[float]:
        """measured words / the matching communication lower bound."""
        lb = self.lower_bound
        if self.measured_words is None or lb is None:
            return None
        return self.measured_words / max(lb, 1.0)

    def why(self) -> str:
        if self.degraded:
            msg = (f"{self.op}: runtime {self.fault} quarantined the "
                   f"primary backend; degraded from {self.requested!r} to "
                   f"{self.chosen!r} (words re-priced)")
        elif not self.fell_back:
            msg = f"{self.op}: ran on requested backend {self.chosen!r}"
        else:
            msg = (f"{self.op}: {self.requested!r} lacks "
                   f"{', '.join(self.missing)}; fell back to {self.chosen!r}")
        if self.plan is not None:
            msg += f"; {self.plan_source} plan"
            tuned = getattr(self.plan, "tuned", None)
            if tuned is not None:
                msg += (f" ({tuned.candidates_timed} candidates timed via "
                        f"{tuned.source}, winner {tuned.winner_seconds:.2e}s "
                        f"vs analytic)")
        if self.measured_words is not None:
            kind = ("inter-device" if self.op.endswith("_dist") else "HBM")
            msg += f"; measured {self.measured_words:.3e} {kind} words"
            if self.bound_ratio is not None:
                msg += (f" = {self.bound_ratio:.2f}x the "
                        f"{self.lower_bound:.3e}-word lower bound")
        if self.audited is not None:
            msg += " (statically audited)"
        return msg


_TRACE: List[List[DispatchDecision]] = []  # stack of active recorders


@contextlib.contextmanager
def record_dispatch():
    """Capture every DispatchDecision made while active (trace API)."""
    log: List[DispatchDecision] = []
    _TRACE.append(log)
    try:
        yield log
    finally:
        # remove by identity: nested recorders hold equal (e.g. empty) lists
        for i, entry in enumerate(_TRACE):
            if entry is log:
                del _TRACE[i]
                break


# ---------------------------------------------------------------------------
# Runtime-failure state: the quarantine table and the fault-injection hook.
# ---------------------------------------------------------------------------

# (op, backend, shape-key) -> {"fault": taxonomy class name, "probe_in": N}.
# Populated by dispatch_call when an entry raises a TransientFault; consulted
# by _resolve_entry so subsequent calls (including jit traces) demote past
# the failing backend. probe_in decrements only on executing dispatches; at
# zero the entry is removed and the primary backend is probed again.
_QUARANTINE: Dict[Tuple[str, str, Any], Dict[str, Any]] = {}

_FAULT_HOOK: Optional[Any] = None  # resilience.faults.DispatchFaultHook
_ENV_FAULTS_CHECKED = False


def set_fault_hook(hook) -> None:
    """Install/remove the campaign dispatch hook (``resilience.faults``)."""
    global _FAULT_HOOK
    _FAULT_HOOK = hook


def _ensure_env_faults() -> None:
    """First-eager-dispatch check of the ``REPRO_FAULTS`` env knob."""
    global _ENV_FAULTS_CHECKED
    if _ENV_FAULTS_CHECKED:
        return
    _ENV_FAULTS_CHECKED = True
    if os.environ.get("REPRO_FAULTS"):
        from repro.resilience.faults import install_env_campaign

        install_env_campaign()


def quarantined() -> Dict[Tuple[str, str, Any], Dict[str, Any]]:
    """A snapshot of the quarantine table (introspection/tests)."""
    return {k: dict(v) for k, v in _QUARANTINE.items()}


def clear_quarantine() -> None:
    """Forget every runtime quarantine (benchmarks reset between runs)."""
    _QUARANTINE.clear()


def _freeze_kw(v):
    """A hashable, deterministic stand-in for one spec kwarg value; None for
    ambient objects (meshes, blockings) that don't shape the quarantine."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, tuple):
        return tuple(_freeze_kw(x) for x in v)
    try:
        return jnp.dtype(v).name  # dtype-likes (jnp.bfloat16, "int8", ...)
    except TypeError:
        return None


def _shape_key(needs: Tuple[str, ...], spec_args: Optional[tuple],
               spec_kw: Optional[dict]):
    """The quarantine granularity: a kernel that faults on one launch
    geometry is demoted for that geometry only, not for the whole op."""
    if spec_args is None:
        return (needs,)
    arrs = tuple((tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", "")))
                 for a in spec_args)
    kws = tuple((k, _freeze_kw(v))
                for k, v in sorted((spec_kw or {}).items()))
    return (needs, arrs, kws)


def _is_tracing(*trees) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(trees))


def _resolve_entry(op: str, ctx: ExecutionContext, dtype: Optional[str],
                   needs: Tuple[str, ...], shape_key: Any = None,
                   probe: bool = False) -> Tuple[OpEntry, DispatchDecision]:
    requested = ctx.resolved_backend()
    name: Optional[str] = requested
    missing: Tuple[str, ...] = ()
    degraded = False
    fault: Optional[str] = None
    for _ in range(MAX_FALLBACK_DEPTH):
        if name is None:
            break
        backend = get_backend(name)
        entry = backend.ops.get(op)
        lacks = (f"op:{op}",) if entry is None else entry.caps.missing(
            dtype=dtype, needs=needs)
        if not lacks:
            q = _QUARANTINE.get((op, name, shape_key))
            if q is not None and probe:
                q["probe_in"] -= 1
                if q["probe_in"] <= 0:  # probe the primary again
                    del _QUARANTINE[(op, name, shape_key)]
                    q = None
            if q is not None:
                missing = missing + (f"fault:{q['fault']}",)
                degraded, fault = True, q["fault"]
                name = entry.degrade_to or backend.fallback
                continue
            decision = DispatchDecision(op=op, requested=requested,
                                        chosen=name, missing=missing,
                                        degraded=degraded, fault=fault)
            return entry, decision
        missing = missing + lacks
        name = backend.fallback
    raise NotImplementedError(
        f"no registered backend can serve op {op!r} "
        f"(requested {requested!r}, dtype={dtype}, needs={needs}; "
        f"missing along the fallback chain: {missing})")


def _attach_plan_and_words(entry: OpEntry, decision: DispatchDecision,
                           ctx: ExecutionContext,
                           spec_args: Optional[tuple],
                           spec_kw: Optional[dict],
                           plan: Optional[Any] = None) -> DispatchDecision:
    """Resolve the entry's plan — explicit ``plan`` > stored tuned winner >
    analytic LP, via the shared ``ctx.plan_with_source`` path — and its
    measured-HBM-words counter (both need only shapes/dtypes, so tracers and
    ShapeDtypeStructs work)."""
    if spec_args is None:
        return decision
    kw = spec_kw or {}
    if plan is not None:
        decision = dataclasses.replace(decision, plan=plan,
                                       plan_source="explicit")
    elif entry.spec_fn is not None:
        resolved, source = ctx.plan_with_source(entry.spec_fn(*spec_args,
                                                              **kw))
        decision = dataclasses.replace(decision, plan=resolved,
                                       plan_source=source)
    if entry.words_fn is not None:
        decision = dataclasses.replace(
            decision,
            measured_words=entry.words_fn(ctx, decision.plan,
                                          *spec_args, **kw))
    return decision


def _maybe_audit(entry: OpEntry, decision: DispatchDecision,
                 ctx: ExecutionContext, spec_args: Optional[tuple],
                 spec_kw: Optional[dict], audit: bool) -> DispatchDecision:
    """Opt-in static audit: build the entry's KernelAccessPlan, abstractly
    interpret it, and stamp the audited word count on the decision. Raises
    ``repro.verify.AuditError`` on any mismatch/violation/hazard. Lazy
    import keeps the hot dispatch path free of the verify machinery."""
    if not audit or spec_args is None or entry.access_plan_fn is None:
        return decision
    from repro.verify import audit as _audit

    ap = entry.access_plan_fn(ctx, decision.plan, *spec_args,
                              **(spec_kw or {}))
    report = _audit.audit_decision(ap, decision, target=ctx.target)
    if not report.ok:
        raise _audit.AuditError(report)
    return dataclasses.replace(decision, audited=report.counted_words)


def resolve(op: str, ctx: Optional[ExecutionContext] = None,
            dtype: Optional[str] = None, needs: Tuple[str, ...] = (),
            spec_args: Optional[tuple] = None, spec_kw: Optional[dict] = None,
            audit: bool = False,
            plan: Optional[Any] = None) -> Tuple[OpEntry, DispatchDecision]:
    """Capability-resolve one call; resolve the entry's plan (explicit
    ``plan=`` > tuned > analytic, stamped as ``plan_source``) and measured
    HBM-word counter if it declares them. ``audit=True`` additionally runs
    the ``repro.verify`` static auditor against the chosen entry's access
    plan (raising on any mismatch or hazard). Quarantine-aware (a runtime-
    quarantined backend is skipped, the decision marked ``degraded``) but
    never consumes quarantine probes — only executing dispatches do."""
    ctx = default_context() if ctx is None else ctx
    needs = tuple(needs)
    entry, decision = _resolve_entry(
        op, ctx, dtype, needs, shape_key=_shape_key(needs, spec_args, spec_kw))
    decision = _attach_plan_and_words(entry, decision, ctx, spec_args,
                                      spec_kw, plan=plan)
    decision = _maybe_audit(entry, decision, ctx, spec_args, spec_kw, audit)
    for log in _TRACE:
        log.append(decision)
    return entry, decision


def explain(op: str, ctx: Optional[ExecutionContext] = None,
            dtype: Optional[str] = None, needs: Tuple[str, ...] = (),
            spec_args: Optional[tuple] = None,
            spec_kw: Optional[dict] = None,
            audit: bool = False,
            plan: Optional[Any] = None) -> DispatchDecision:
    """The decision ``resolve`` would make, without executing anything.
    ``spec_args``/``spec_kw`` mirror ``resolve`` so the reported plan and
    measured words are the ones the dispatched kernel would consume (e.g.
    conv2d needs stride=); ``jax.ShapeDtypeStruct`` spec_args work since
    only shapes/dtypes are consulted. The decision's ``plan_source`` tells
    tuned from analytic plans apart (and ``why()`` narrates the tuning
    provenance). ``audit=True`` runs the static communication auditor and
    stamps ``DispatchDecision.audited``."""
    ctx = default_context() if ctx is None else ctx
    needs = tuple(needs)
    entry, decision = _resolve_entry(
        op, ctx, dtype, needs, shape_key=_shape_key(needs, spec_args, spec_kw))
    decision = _attach_plan_and_words(entry, decision, ctx, spec_args,
                                      spec_kw, plan=plan)
    return _maybe_audit(entry, decision, ctx, spec_args, spec_kw, audit)


def dispatch_call(op: str, ctx: ExecutionContext, dtype: Optional[str],
                  needs: Tuple[str, ...], spec_args: tuple,
                  spec_kw: Optional[dict] = None,
                  call_args: Optional[tuple] = None,
                  call_kw: Optional[dict] = None,
                  plan: Optional[Any] = None):
    """Resolve AND execute one op call with runtime-failure fallback.
    ``plan=`` forces an explicit ExecutionPlan onto the chosen entry (the
    autotuner's candidate-timing path); omitted, the shared resolution path
    picks tuned-then-analytic.

    The public op wrappers funnel through here: resolve (quarantine-aware,
    consuming probes), price the plan/words, run the entry — through the
    fault-injection hook when a campaign is active — and on a
    ``TransientFault`` quarantine the failing ``(op, backend, shape-key)``
    and re-resolve. An entry with a ``degrade_to``/fallback chain demotes;
    a terminal entry retries in place. ``FatalFault`` (and anything not in
    the taxonomy) propagates. The decision lands in ``record_dispatch``
    logs only for the execution that actually served the call."""
    _ensure_env_faults()
    spec_kw = spec_kw or {}
    call_args = spec_args if call_args is None else call_args
    call_kw = dict(spec_kw) if call_kw is None else call_kw
    key = _shape_key(needs, spec_args, spec_kw)
    last_fault: Optional[TransientFault] = None
    for _ in range(MAX_RUNTIME_ATTEMPTS):
        entry, decision = _resolve_entry(op, ctx, dtype, needs,
                                         shape_key=key, probe=True)
        decision = _attach_plan_and_words(entry, decision, ctx,
                                          spec_args, spec_kw, plan=plan)

        def runner(entry=entry, decision=decision):
            return entry.fn(ctx, decision.plan, *call_args, **call_kw)

        hook = _FAULT_HOOK
        try:
            if hook is not None:
                out = hook.run(op, decision.chosen, runner,
                               tracing=_is_tracing(call_args, call_kw))
            else:
                out = runner()
        except TransientFault as e:
            last_fault = e
            nxt = entry.degrade_to or get_backend(decision.chosen).fallback
            inj = getattr(e, "injection", None)
            if inj is not None and inj.resolution is None:
                inj.resolution = "degraded" if nxt is not None else "retried"
            if nxt is not None:
                _QUARANTINE[(op, decision.chosen, key)] = {
                    "fault": type(e).__name__,
                    "probe_in": QUARANTINE_PROBE_AFTER}
            continue  # re-resolve: demote past the quarantine, or retry
        for log in _TRACE:
            log.append(decision)
        return out
    raise last_fault


# ---------------------------------------------------------------------------
# Public ops. Feature extraction happens here, once, for every caller.
# ---------------------------------------------------------------------------

def _is_static_int(v) -> bool:
    return isinstance(v, (int, np.integer))


def matmul(a, b, ctx: Optional[ExecutionContext] = None, out_dtype=None):
    """C[m,n] = A @ B through the dispatched backend; ``out_dtype`` defaults
    to the target precision policy's accumulator dtype."""
    ctx = default_context() if ctx is None else ctx
    out_dtype = out_dtype or ctx.acc_dtype
    # out_dtype rides in spec_kw so the measured-words counter charges the
    # store stream at the dtype the kernel actually writes
    return dispatch_call("matmul", ctx, str(a.dtype), (), (a, b),
                         spec_kw={"out_dtype": out_dtype})


def conv2d(x, w, stride=(1, 1), ctx: Optional[ExecutionContext] = None,
           out_dtype=None):
    """Direct 7NL convolution (VALID padding) through the dispatched backend."""
    ctx = default_context() if ctx is None else ctx
    out_dtype = out_dtype or ctx.acc_dtype
    return dispatch_call("conv2d", ctx, str(x.dtype), (), (x, w),
                         spec_kw={"stride": stride, "out_dtype": out_dtype})


def matmul_q(a, b, scale, ctx: Optional[ExecutionContext] = None,
             out_dtype=None):
    """Quantized GEMM: ``a``/``b`` int8 (from
    ``repro.quant.quantize_matmul_operands``), ``scale`` the folded (1, n)
    f32 per-column dequant scales. Streams stay int8 into VMEM; the f32
    accumulator is scaled once at the store. ``out_dtype`` defaults to bf16
    (``repro.quant.INT8_SPEC``), not the context accumulator — the narrower
    store is half of what moves the measured words."""
    ctx = default_context() if ctx is None else ctx
    out_dtype = out_dtype or jnp.bfloat16
    return dispatch_call("matmul_q", ctx, str(a.dtype), (), (a, b, scale),
                         spec_kw={"out_dtype": out_dtype})


def conv2d_q(x, w, scale, stride=(1, 1),
             ctx: Optional[ExecutionContext] = None, out_dtype=None):
    """Quantized direct conv2d (VALID padding): int8 ``x``/``w`` plus the
    folded (1, c_O) f32 scale from ``repro.quant.quantize_conv_operands``.
    ``out_dtype`` defaults to bf16 (see :func:`matmul_q`)."""
    ctx = default_context() if ctx is None else ctx
    out_dtype = out_dtype or jnp.bfloat16
    return dispatch_call("conv2d_q", ctx, str(x.dtype), (), (x, w, scale),
                         spec_kw={"stride": stride, "out_dtype": out_dtype})


def conv2d_dist(x, w, stride=(1, 1), blocking=None, mesh=None,
                ctx: Optional[ExecutionContext] = None, out_dtype=None):
    """Distributed halo-exchange conv2d over a device mesh (paper §4.2).

    ``blocking`` is the ``ParallelBlocking`` processor grid (LP-chosen over
    all available devices when omitted) and ``mesh`` the matching conv mesh
    (``launch.make_conv_mesh(blocking)`` when omitted). The backend picks the
    *shard-local* kernel (``pallas`` = the LP-tiled PR-4 kernel); the
    decision's ``measured_words`` are the per-device inter-device words
    (halo + psum), ratioed against the plan's Thm 2.2/2.3 parallel bound."""
    ctx = default_context() if ctx is None else ctx
    out_dtype = out_dtype or ctx.acc_dtype
    return dispatch_call(
        "conv2d_dist", ctx, str(x.dtype), (), (x, w),
        spec_kw={"stride": stride, "out_dtype": out_dtype,
                 "blocking": blocking, "mesh": mesh})


def conv1d_causal(x, w, ctx: Optional[ExecutionContext] = None):
    """Causal depthwise conv1d (the mamba/xLSTM short convolution)."""
    ctx = default_context() if ctx is None else ctx
    return dispatch_call("conv1d_causal", ctx, str(x.dtype), (), (x, w))


def grouped_matmul(x, w, group_sizes, ctx: Optional[ExecutionContext] = None):
    """``out[r] = x[r] @ w[g]`` for the rows ``r`` of group ``g``: ``x`` is
    (M, K) with rows sorted by group, ``w`` (G, K, N), ``group_sizes`` (G,)
    int32, each a multiple of ``kernels.grouped_matmul.ROW_TILE``. Rows past
    ``sum(group_sizes)`` are unspecified; the result is in ``x``'s type,
    accumulated in float32. The pallas entry streams each group's weights
    once and skips groups with no rows; the xla entry is
    ``jax.lax.ragged_dot``."""
    ctx = default_context() if ctx is None else ctx
    return dispatch_call("grouped_matmul", ctx, str(x.dtype), (),
                         (x, w, group_sizes))


def attention_needs(q_offset=0, key_mask=None) -> Tuple[str, ...]:
    """Required capability flags of one attention call (shared with tests)."""
    needs = []
    if not _is_static_int(q_offset):
        needs.append("per_row_q_offset" if getattr(q_offset, "ndim", 0)
                     else "dynamic_q_offset")
    if key_mask is not None:
        needs.append("key_mask")
    return tuple(needs)


def attention(q, k, v, causal: bool = True, q_offset=0, key_mask=None,
              ctx: Optional[ExecutionContext] = None):
    """GQA attention, (B, H, L, Dh) layout; Hkv divides H.

    ``q_offset``: absolute position of the first query — a static python int
    (train/prefill), a traced scalar (lockstep decode), or a (B,) vector
    (continuous-batching decode, every slot at its own depth). ``key_mask``
    is an optional (B, Lk) validity mask over the keys (padded prefill).
    Backends that cannot serve the masked variant (the Pallas flash kernel)
    fall back by declared capability; traced and per-row offsets ride the
    flash kernel's scalar-prefetch path."""
    ctx = default_context() if ctx is None else ctx
    return dispatch_call("attention", ctx, str(q.dtype),
                         attention_needs(q_offset, key_mask), (q, k, v),
                         call_kw={"causal": causal, "q_offset": q_offset,
                                  "key_mask": key_mask})


def attention_decode(q, kp, vp, tables, lengths, layer, k_new, v_new,
                     ctx: Optional[ExecutionContext] = None):
    """One paged decode step of one layer, updating the pools in place:
    ``q`` is (B, H, 1, hd); ``kp``/``vp`` the stacked (layers, num_blocks,
    KV, block_size, hp) pools of every layer; ``tables`` the (B, w) int32
    physical-block ids backing each row's logical positions; ``lengths``
    the (B,) valid cache lengths, the current token included; ``layer`` the
    int32 layer index; ``k_new``/``v_new`` the step's (B, KV, hd) rows.
    Row b's rows land in ``layer`` at position ``lengths[b] - 1``, then the
    row attends over its positions. Returns ``(out, kp, vp)``.

    The pallas entry streams each row's live pages and copies back only the
    page it wrote, the pools aliased in place; the xla entry scatters and
    gathers to a contiguous view first — the measured-words gap between
    them is the point of the paged subsystem. The pools' heads may be
    zero-padded past q's ``hd`` (:func:`pool_head_dim`); the result and the
    scores' 1/sqrt(hd) keep q's."""
    ctx = default_context() if ctx is None else ctx
    return dispatch_call("attention_decode", ctx, str(q.dtype), (),
                         (q, kp, vp, tables, lengths, layer, k_new, v_new))


def pool_head_dim(op: str, hd: int,
                  ctx: Optional[ExecutionContext] = None) -> int:
    """The head dim of a paged K/V pool that ``op`` reads under ``ctx``:
    ``hd``, or ``hd`` rounded up to the target's lanes where the chosen
    entry copies whole pages (``OpEntry.lane_pages``). Heads past ``hd``
    are zeros."""
    ctx = default_context() if ctx is None else ctx
    entry, _ = _resolve_entry(op, ctx, None, ())
    if not entry.lane_pages:
        return hd
    lanes = ctx.target.align_lane
    return -(-hd // lanes) * lanes


def attention_decode_quant(q, kp, ks, vp, vs, tables, lengths, layer,
                           k_new, v_new,
                           ctx: Optional[ExecutionContext] = None):
    """One paged decode step of one layer against int8-quantized pools,
    updating them as :func:`attention_decode` does.

    ``kp``/``vp`` are the stacked (layers, num_blocks, KV, block_size, hd)
    int8 pools and ``ks``/``vs`` their (layers, num_blocks, KV, block_size)
    f32 per-(block, head, position) scales (written together by the
    engine's quantizing insert); the step's float rows are quantized per
    (row, kv head) as they land. Returns ``(out, kp, ks, vp, vs)``.
    Registered on the xla backend only — any requested backend reaches it
    through the fallback chain — since the interesting quantity here is the
    *pool's* halved stream width (the plan's p_F ~ 0.25 + 1/hd), not the
    gather kernel."""
    ctx = default_context() if ctx is None else ctx
    return dispatch_call("attention_decode_quant", ctx, str(q.dtype), (),
                         (q, kp, ks, vp, vs, tables, lengths, layer, k_new,
                          v_new))
