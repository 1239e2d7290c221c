"""``repro.plan`` — the single planning subsystem (HardwareTarget -> ExecutionPlan).

The paper's optimization discipline — solve the HBL-derived blocking LP
against a memory-hierarchy model, then lower the solution to tilings and
processor grids (§3.2 eq. 6, §4.2, §5) — behind one front door:

    from repro.plan import ConvSpec, Planner, TPU_V5E

    planner = Planner(TPU_V5E)          # optional: quant=..., autotune=True
    ep = planner.plan(ConvSpec(N=32, c_I=64, c_O=64, w_O=56, h_O=56,
                               w_F=3, h_F=3))
    ep.tiles          # (bN, b_cI, b_cO, b_hO, b_wO) for the Pallas kernel
    ep.comm_volume    # modeled HBM<->VMEM words
    ep.efficiency     # vs the Thm 2.1 lower bound
    ep.sharding       # PartitionSpecs when the target has mesh axes

    planner.autotune(op)   # measured frontier search (repro.plan.autotune)
    Planner.cache.save(p)  # persist plans + tuning records; .load/.clear/.size

Kernels take ``ctx=ExecutionContext(...)``; the module-level ``plan()`` /
``*_plan_cache()`` functions and the kernels' ``plan=``/``target=`` kwargs
are one-PR deprecation shims (messages start with "legacy" so CI can promote
them to errors). `core.tiling` / `core.sharding_opt` remain the planner's
low-level building blocks.
"""

from .ops import (  # noqa: F401
    AttentionSpec,
    ConvSpec,
    MatmulSpec,
    OpSpec,
    as_op_spec,
)
from .planner import (  # noqa: F401
    PLAN_FORMAT_VERSION,
    ExecutionPlan,
    ParallelSection,
    PlanCache,
    Planner,
    TunedSection,
    analytic_plan,
    clear_plan_cache,
    load_plan_cache,
    plan,
    plan_cache_size,
    register_plan_audit_hook,
    resolve_kernel_plan,
    resolve_plan,
    save_plan_cache,
    warn_legacy_kernel_kwargs,
)
from .autotune import (  # noqa: F401
    AutotunePolicy,
    TuningRecord,
    predicted_seconds,
    target_fingerprint,
)
from .target import (  # noqa: F401
    CPU_INTERPRET,
    GEMMINI,
    TARGETS,
    TPU_V5E,
    HardwareTarget,
    default_target,
    get_target,
    pallas_interpret,
)
