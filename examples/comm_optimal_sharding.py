"""The paper's parallel blocking LP driving real jax shardings.

Builds an 8-fake-device mesh, asks the unified ``repro.plan`` planner (a
mesh-bearing HardwareTarget makes ``plan()`` attach a ShardingPlan) for the
comm-minimizing loop-axis -> mesh-axis binding of a convolution and of an LM
GEMM, then actually executes the conv under those NamedShardings and
cross-checks the result against the unsharded oracle.

    PYTHONPATH=src python examples/comm_optimal_sharding.py
"""

from repro.launch import fake_devices, make_host_mesh

fake_devices(8)  # before any jax device query

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.kernels.ref import conv2d_ref  # noqa: E402
from repro.plan import ConvSpec, MatmulSpec, TPU_V5E, plan as make_plan  # noqa: E402


def main():
    mesh = make_host_mesh(data=4, model=2)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    target = TPU_V5E.with_mesh((("data", 4), ("model", 2)))
    ep = make_plan(ConvSpec(N=8, c_I=16, c_O=32, w_O=14, h_O=14, w_F=3, h_F=3),
                   target)
    plan = ep.sharding
    print(f"conv binding: {plan.binding} "
          f"(modeled {plan.comm_per_processor:.3e} words/chip)")
    print(f"  input  spec {plan.input_spec}")
    print(f"  filter spec {plan.filter_spec}")
    print(f"  output spec {plan.output_spec}")

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 16, 16, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16, 3, 3), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(*plan.input_spec)))
    # filter layout is OIHW; plan.filter_spec is (cI, cO, ...) -> transpose
    fs = (plan.filter_spec[1], plan.filter_spec[0]) + plan.filter_spec[2:]
    ws = jax.device_put(w, NamedSharding(mesh, P(*fs)))

    with mesh:
        out = jax.jit(conv2d_ref)(xs, ws)
    ref = conv2d_ref(x, w)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"sharded conv vs oracle |err| = {err:.2e}")
    assert err < 1e-4

    gplan = make_plan(MatmulSpec(4096, 2048, 512), target).sharding
    print(f"\nGEMM (4096x2048x512) binding: {gplan.binding} "
          f"-> A rows on 'data', B cols on 'model' (Megatron-style)")
    print("OK")


if __name__ == "__main__":
    main()
