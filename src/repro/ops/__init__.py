"""``repro.ops`` — backend-registry op dispatch (ExecutionContext -> Backend
-> kernel).

The execution-layer counterpart of ``repro.plan``: where ``plan`` decides
*how* an op is tiled/sharded for a HardwareTarget, ``ops`` decides *which
implementation runs* — a per-(op, target) decision, matching the paper's
premise that mixed-precision word sizes change the Thm 2.1 bound and
therefore the optimal execution strategy.

    from repro import ops
    from repro.ops import ExecutionContext
    from repro.plan import TPU_V5E

    ctx = ExecutionContext(target=TPU_V5E)           # -> pallas by default
    y = ops.attention(q, k, v, ctx=ctx)              # flash kernel, LP blocks
    y = ops.attention(q, k, v, q_offset=idx, ctx=ctx)  # still pallas: traced
                                                       # offsets scalar-prefetch
    y = ops.attention_decode(q, kp, vp, tables, lens, ctx=ctx)  # paged decode
    ops.explain("attention", ctx, needs=("key_mask",)).chosen  # -> "xla"
                                                     # (fallback *by capability*)

Backends are registered in ``repro.ops.registry`` (``xla``, ``pallas``, and
the ``im2col`` conv baseline); each op entry declares capabilities (accepted
dtypes, per-row ``q_offset``, key masks) and the dispatcher walks the
fallback chain until one covers the call. ``ExecutionContext`` carries the
HardwareTarget (precision policy + plan cache handle) and an optional
backend override — it supersedes the ``use_pallas``
booleans that used to thread through the model stack (the last shim,
``kernels/ops.py``, is gone). Backend selection from the environment:
``REPRO_BACKEND=xla|pallas|im2col`` — the only environment knob (the PR-3
``REPRO_USE_PALLAS`` variable is retired and now ignored).

Instrumented entries also declare a measured-words counter: every conv and
matmul ``DispatchDecision`` reports the words its launch geometry moves next
to the matching paper bound (``decision.measured_words``,
``decision.bound_ratio``, ``ops.explain(...).why()``) — HBM words vs. the
Thm 2.1 bound for single-device ops, per-device *inter-device* words vs. the
Thm 2.2/2.3 parallel bound for ``conv2d_dist`` (the distributed
halo-exchange conv of ``repro.distributed``, whose backend choice selects
the shard-local kernel).
"""

from .context import (  # noqa: F401
    BACKEND_ENV,
    ExecutionContext,
    default_context,
    dtype_for_words,
    env_backend,
)
from .dispatch import (  # noqa: F401
    QUARANTINE_PROBE_AFTER,
    DispatchDecision,
    attention,
    attention_decode,
    attention_decode_quant,
    attention_needs,
    clear_quarantine,
    conv1d_causal,
    conv2d,
    conv2d_dist,
    conv2d_q,
    dispatch_call,
    explain,
    grouped_matmul,
    matmul,
    matmul_q,
    pool_head_dim,
    quarantined,
    record_dispatch,
    resolve,
    set_fault_hook,
)
from .registry import (  # noqa: F401
    Backend,
    OpCapabilities,
    OpEntry,
    backends,
    get_backend,
    register_backend,
    registered_ops,
    xla_attention,
    xla_attention_decode,
    xla_attention_decode_quant,
)
