"""ExecutionContext: the single execution-policy object threaded through the
model stack.

The paper's premise is that the *same* op should execute differently per
target — mixed-precision word sizes change the Thm 2.1 bound and therefore the
optimal tiling — so "which implementation runs, with which tiles, at which
precision" is a per-(op, target) decision. ``ExecutionContext`` bundles the
three inputs of that decision:

  * ``target``  - the :class:`repro.plan.HardwareTarget` whose memory model the
                  blocking LP plans against and whose ``precision`` policy sets
                  stream/accumulator dtypes (default: the attached device's,
                  :func:`repro.plan.default_target`);
  * ``backend`` - an explicit backend override (``"xla"`` | ``"pallas"``).
                  ``None`` defers to the ``REPRO_BACKEND`` environment variable
                  and then to the target's own default;
  * ``autotune`` - measured-autotune policy (``None``/``False`` = off,
                  ``True`` = default :class:`repro.plan.AutotunePolicy`, or a
                  policy instance). When set, plan resolution may run one
                  frontier search per (op, target) and then serve the tuned
                  winner from the TuningRecord store.

Plans are resolved through ``repro.plan.resolve_plan`` — the one shared path
(explicit plan > stored tuned plan > analytic LP plan) behind ``ctx.plan()``,
``ops.explain`` and the kernels' ``resolve_kernel_plan`` — backed by the
process-wide memoized cache in ``repro.plan.planner``, so every consumer of
one context converges on identical ``ExecutionPlan`` objects.

Backend resolution order: explicit ``ctx.backend`` > ``REPRO_BACKEND`` env var
> the target default. Whether a Pallas kernel runs interpreted is not part of
the context: the kernels ask ``repro.plan.pallas_interpret`` (the CPU
interprets, an accelerator compiles). ``REPRO_BACKEND`` is the only
environment knob.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from repro.plan import HardwareTarget, default_target

BACKEND_ENV = "REPRO_BACKEND"

# Paper word-widths (units of 32-bit words) -> jnp dtypes. The precision
# policy of a HardwareTarget speaks words; kernels speak dtypes.
_WORD_DTYPES = {1.0: jnp.float32, 0.5: jnp.bfloat16, 0.25: jnp.int8}


def dtype_for_words(words: float):
    """The jnp dtype of a paper word-width (1.0 -> f32, 0.5 -> bf16, ...)."""
    try:
        return _WORD_DTYPES[float(words)]
    except KeyError:
        raise ValueError(f"no dtype for precision {words} words; "
                         f"known: {sorted(_WORD_DTYPES)}")


def env_backend() -> Optional[str]:
    """Backend requested via ``REPRO_BACKEND=xla|pallas|im2col``, or None."""
    name = os.environ.get(BACKEND_ENV)
    if name:
        name = name.strip().lower()
        if name not in ("xla", "pallas", "im2col"):
            raise ValueError(
                f"{BACKEND_ENV}={name!r} is not a known backend "
                "(expected 'xla', 'pallas', or 'im2col')")
        return name
    return None


@dataclasses.dataclass(frozen=True)
class ExecutionContext:
    """HardwareTarget + precision policy + backend override + plan handle.

    Frozen and hashable so it can key jit static arguments and the serving
    engine's compiled-step cache, exactly as the old ``use_pallas`` bool did.
    """

    target: HardwareTarget = dataclasses.field(default_factory=default_target)
    backend: Optional[str] = None  # "xla" | "pallas" | None (resolve)
    autotune: Any = None  # None/False | True | repro.plan.AutotunePolicy

    # -- backend resolution ---------------------------------------------------
    def resolved_backend(self) -> str:
        if self.backend is not None:
            return self.backend
        return env_backend() or ("pallas" if self.target.use_pallas else "xla")

    def resolved(self) -> "ExecutionContext":
        """Pin the backend choice (env var read once, here) so the context
        can key long-lived caches without depending on ambient state."""
        return dataclasses.replace(self, backend=self.resolved_backend())

    # -- builders -------------------------------------------------------------
    def with_backend(self, name: Optional[str]) -> "ExecutionContext":
        return dataclasses.replace(self, backend=name)

    @classmethod
    def from_target(cls, target: HardwareTarget,
                    backend: Optional[str] = None) -> "ExecutionContext":
        return cls(target=target, backend=backend)

    # -- plan-cache handle ----------------------------------------------------
    def plan(self, op, explicit=None):
        """Resolve the ExecutionPlan for ``op`` on this context's target via
        the shared resolution path (explicit > tuned > analytic), honoring
        this context's autotune policy."""
        return self.plan_with_source(op, explicit=explicit)[0]

    def plan_with_source(self, op, explicit=None) -> Tuple[Any, str]:
        """``(plan, source)`` with source one of ``"explicit"`` | ``"tuned"``
        | ``"analytic"`` — the same tuple ``DispatchDecision.plan_source``
        reports."""
        from repro.plan import resolve_plan

        return resolve_plan(op, self.target, explicit=explicit,
                            autotune=self.autotune)

    # -- precision policy -----------------------------------------------------
    @property
    def stream_dtype(self):
        """Input/filter stream dtype from the target's precision policy."""
        return dtype_for_words(self.target.precision.p_I)

    @property
    def acc_dtype(self):
        """Output/accumulator dtype from the target's precision policy (the
        default ``out_dtype`` of every dispatched op)."""
        return dtype_for_words(self.target.precision.p_O)


def default_context() -> ExecutionContext:
    """The context used when a consumer passes ``ctx=None``: the attached
    device's target, whose default backend runs there (Pallas on the TPU,
    XLA on the CPU) unless ``REPRO_BACKEND`` asks for another."""
    return ExecutionContext(target=default_target())
