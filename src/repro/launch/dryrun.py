import argparse
import dataclasses
import json
import math
import os
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.configs import ARCH_IDS, SKIPS, get_config
from repro.launch import specs as sp
from repro.launch.devices import fake_devices
from repro.launch.mesh import make_production_mesh
from repro.models import sharding as shd
from repro.models import transformer as T
from repro.models.config import LM_SHAPES
from repro.train.optimizer import AdamWConfig, AdamWState
from repro.train.trainer import TrainConfig, make_train_step


def ensure_dryrun_devices() -> int:
    """Request the dry-run fake-device count (``REPRO_DRYRUN_DEVICES``,
    default 512) through ``launch.fake_devices``. Called on the driver paths
    that build their own production mesh — not at import, so importing this
    module no longer mutates ``XLA_FLAGS`` or locks the jax device count for
    embedding processes (tests pass an explicit ``mesh=`` instead)."""
    return fake_devices(int(os.environ.get("REPRO_DRYRUN_DEVICES", "512")))

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


# ---------------------------------------------------------------------------
# Step builders (what gets lowered per cell kind)
# ---------------------------------------------------------------------------

def build_train_step(cfg, mesh, n_groups: int, act_seq_shard: bool = True,
                     loss_chunks: Optional[int] = None, remat: bool = True):
    ba = shd.batch_axes(mesh)
    act = P(ba, "model", None) if act_seq_shard else P(ba, None, None)
    if loss_chunks is None:
        loss_chunks = 16 if cfg.vocab_size > 32000 else 4
    tc = TrainConfig(remat=remat, n_groups=n_groups,
                     loss_chunks=loss_chunks, act_spec=act)
    oc = AdamWConfig()
    step = make_train_step(cfg, oc, tc)

    def train_step(params, opt_state, batch):
        return step(params, opt_state, batch)

    return train_step


def build_prefill_step(cfg, n_groups: int, act_spec=None):
    def prefill_step(params, cache, batch):
        if cfg.causal:
            logits, cache, _ = T.forward(
                params, cfg, tokens=batch.get("tokens"),
                embeds=batch.get("embeds"), cache=cache,
                cache_index=jnp.zeros((), jnp.int32), n_groups=n_groups,
                act_spec=act_spec)
            return logits[:, -1], cache
        # encoder: full bidirectional forward, no cache
        logits, _, _ = T.forward(params, cfg, tokens=batch.get("tokens"),
                                 embeds=batch.get("embeds"),
                                 n_groups=n_groups, act_spec=act_spec)
        return logits, cache
    return prefill_step


def build_decode_step(cfg, n_groups: int):
    def serve_step(params, cache, token, index):
        logits, cache, _ = T.forward(params, cfg, tokens=token, cache=cache,
                                     cache_index=index, decode=True,
                                     n_groups=n_groups)
        return logits[:, -1], cache
    return serve_step


# ---------------------------------------------------------------------------
# Sharding assembly per cell
# ---------------------------------------------------------------------------

def cell_shardings(arch: str, shape_name: str, mesh, cfg=None):
    cfg = cfg or get_config(arch)
    shape = LM_SHAPES[shape_name]
    ba = shd.batch_axes(mesh)
    pspec = shd.param_specs(cfg)
    out = {}
    if shape.kind == "train":
        out["params"] = pspec
        out["opt_state"] = AdamWState(step=P(), m=pspec, v=pspec)
        bspecs = {}
        if cfg.inputs_are_embeddings:
            bspecs["embeds"] = P(ba, "model", None)
            bspecs["labels" if not cfg.causal else "tokens"] = P(ba, None)
        else:
            bspecs["tokens"] = P(ba, None)
        out["batch"] = bspecs
    elif shape.kind == "prefill":
        out["params"] = pspec
        out["cache"] = jax.tree.map(
            lambda s: s,
            shd.cache_specs(cfg, mesh, shape.global_batch, shape.seq_len),
            is_leaf=lambda x: isinstance(x, P))
        bspecs = {}
        if cfg.inputs_are_embeddings:
            bspecs["embeds"] = P(ba, None, None)
            bspecs["labels" if not cfg.causal else "tokens"] = P(ba, None)
        else:
            bspecs["tokens"] = P(ba, None)
        out["batch"] = bspecs
    else:  # decode
        dsize = math.prod(mesh.shape[a] for a in shd.data_axes(mesh))
        tok_spec = P(ba, None) if shape.global_batch % max(dsize, 1) == 0 \
            and shape.global_batch > 1 else P(None, None)
        out["params"] = pspec
        out["cache"] = shd.cache_specs(cfg, mesh, shape.global_batch,
                                       shape.seq_len)
        out["token"] = tok_spec
        out["index"] = P()
    return out


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------

def _recurrent_heads(cfg) -> int:
    """Largest per-head chunk-decay width among recurrent blocks (0 if none)."""
    h = 0
    for kind in cfg.pattern:
        if kind == "mamba":
            di = cfg.d_inner
            h = max(h, di // cfg.hd if di % cfg.hd == 0 else 1)
        elif kind == "mlstm":
            h = max(h, cfg.n_heads)
    return h


def choose_chunk(cfg, shape) -> int:
    """Dry-run chunk size for recurrent blocks: the scans are fully unrolled
    (REPRO_UNROLL_SCANS - see scan_util), so the chunk count nc = L/c directly
    multiplies compile time, while the intra-chunk decay tensor (B, c, c, H)
    multiplies the memory footprint. Pick the largest c with
    B*c^2*H <= ~3.8e10 elements (~300 MB f32/device at 512 chips), nc <= 32,
    c in [256, 4096]."""
    H = _recurrent_heads(cfg)
    if H == 0 or shape.kind == "decode":
        return cfg.chunk_size
    L, B = shape.seq_len, shape.global_batch
    budget = 1.4e11  # global f32 elements for one decay tensor (~1GB/chip)
    c = int(math.sqrt(budget / max(B * H, 1)))
    c = max(256, min(c, 4096, L))
    # snap to a power-of-two divisor of L with nc <= 16
    c2 = 256
    while c2 * 2 <= c and L % (c2 * 2) == 0:
        c2 *= 2
    while L // c2 > 16:
        c2 *= 2
    return min(c2, L)


def _extrapolate_cell(arch: str, shape_name: str, multi_pod: bool,
                      save: bool, verbose: bool, mesh, variant: str,
                      ov: dict) -> dict:
    """Two-point repeat extrapolation for compile-heavy recurrent cells.

    The unrolled program is homogeneous in pattern repeats, so every additive
    cost (FLOPs, bytes, per-kind wire bytes) is exactly affine in R:
    cost(R) = cost(2) + (R-2) * (cost(2) - cost(1)). We compile R=1 and R=2
    and extrapolate to the real depth; numerics are untouched (this is a
    cost-model evaluation, the full-depth program still lowers - decode cells
    prove the stacked params/cache shard).
    """
    cfg_full = get_config(arch)
    unit = len(cfg_full.pattern)
    R = cfg_full.repeats
    recs = []
    for r in (1, 2):
        ov_r = dict(ov)
        ov_r["_n_layers"] = unit * r
        recs.append(run_cell(arch, shape_name, multi_pod=multi_pod,
                             save=False, verbose=False, mesh=mesh,
                             variant=variant, overrides=ov_r))
    one, two = recs
    out = dict(two)

    def lin(a, b):
        return b + (R - 2) * (b - a)

    for key in ("hlo_flops", "hlo_bytes", "wire_bytes_per_chip"):
        out[key] = lin(one[key], two[key])
    out["collectives"] = {k: lin(one["collectives"][k], two["collectives"][k])
                          for k in two["collectives"]}
    out["bytes_per_device"] = {
        k: (lin(one["bytes_per_device"][k], two["bytes_per_device"][k])
            if k in ("argument_bytes", "output_bytes")
            else two["bytes_per_device"][k])  # temps: buffer-reuse bound
        for k in two["bytes_per_device"]}
    out["model_flops"] = rl.model_flops(arch, shape_name)
    chips = out["chips"]
    peaks = rl.chip_peaks(out["device_kind"])
    out["compute_s"] = out["hlo_flops"] / (chips * peaks.flops_bf16)
    out["memory_s"] = out["hlo_bytes"] / (chips * peaks.hbm_bw)
    out["collective_s"] = out["wire_bytes_per_chip"] / peaks.ici_bw
    terms = {"compute": out["compute_s"], "memory": out["memory_s"],
             "collective": out["collective_s"]}
    out["dominant"] = max(terms, key=terms.get)
    out["step_time_s"] = max(terms.values())
    out["useful_flops_frac"] = out["model_flops"] / max(out["hlo_flops"], 1.0)
    out["mfu"] = out["model_flops"] / (
        out["step_time_s"] * chips * peaks.flops_bf16 + 1e-30)
    out["extrapolated"] = f"R=1,2 -> R={R}"
    mesh_name = out["mesh"]
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] (extrapolated R={R}) "
              f"compute={out['compute_s']*1e3:.2f}ms "
              f"memory={out['memory_s']*1e3:.2f}ms "
              f"collective={out['collective_s']*1e3:.2f}ms "
              f"dominant={out['dominant']} mfu={out['mfu']:.3f}", flush=True)
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        suffix = "" if variant == "base" else f"__{variant}"
        fn = os.path.join(RESULTS_DIR,
                          f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(out, f, indent=1)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             save: bool = True, verbose: bool = True, mesh=None,
             variant: str = "base", overrides: Optional[dict] = None) -> dict:
    """``overrides`` (perf-iteration knobs, recorded under ``variant``):
        act_seq_shard: bool   sequence-parallel activations (default True)
        loss_chunks: int      chunked cross-entropy chunk count
        remat: bool           scan-body rematerialization (default True)
        param_dtype: str      "float32" (default) | "bfloat16" train params
        chunk_size: int       recurrent-block chunk length
        moe_groups: int       MoE dispatch group count
        cache_seq_axis: str   "model" (default) | "none" decode KV layout
    """
    ov = overrides or {}
    shape = LM_SHAPES[shape_name]
    cfg = get_config(arch)
    if ("_n_layers" not in ov and shape.kind != "decode"
            and _recurrent_heads(cfg) > 0 and cfg.repeats > 2
            and os.environ.get("REPRO_NO_EXTRAPOLATE", "0") != "1"):
        return _extrapolate_cell(arch, shape_name, multi_pod, save, verbose,
                                 mesh, variant, ov)
    if mesh is None:
        ensure_dryrun_devices()
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    if "_n_layers" in ov:
        cfg = dataclasses.replace(cfg, n_layers=ov["_n_layers"])
    cfg = dataclasses.replace(
        cfg, chunk_size=ov.get("chunk_size", choose_chunk(cfg, shape)))
    if "param_dtype" in ov and shape.kind == "train":
        cfg = dataclasses.replace(cfg, param_dtype=ov["param_dtype"])
    if ov.get("moe_shard_hints"):
        cfg = dataclasses.replace(cfg, moe_shard_hints=True)
    if ov.get("fused_kv_cache"):
        cfg = dataclasses.replace(cfg, fused_kv_cache=True)
    if "compute_dtype" in ov:
        cfg = dataclasses.replace(cfg, compute_dtype=ov["compute_dtype"])
    n_groups = ov.get("moe_groups", sp.n_groups_for(shape, chips))

    inputs = sp.input_specs(arch, shape_name, cfg)
    if "param_dtype" in ov and shape.kind == "train":
        inputs["params"] = sp.param_structs(arch, ov["param_dtype"], cfg)
    specs = cell_shardings(arch, shape_name, mesh, cfg)
    if ov.get("cache_seq_axis") == "none" and "cache" in specs:
        specs["cache"] = jax.tree.map(
            lambda s: P(*[None if ax == "model" else ax for ax in s]),
            specs["cache"], is_leaf=lambda x: isinstance(x, P))
    in_shardings = _named(mesh, specs)

    if shape.kind == "train":
        step = build_train_step(cfg, mesh, n_groups,
                                act_seq_shard=ov.get("act_seq_shard", True),
                                loss_chunks=ov.get("loss_chunks"),
                                remat=ov.get("remat", True))
        args = (inputs["params"], inputs["opt_state"], inputs["batch"])
        in_sh = (in_shardings["params"], in_shardings["opt_state"],
                 in_shardings["batch"])
        out_sh = (in_shardings["params"], in_shardings["opt_state"], None)
    elif shape.kind == "prefill":
        ba = shd.batch_axes(mesh)
        act = P(ba, "model", None) if ov.get("act_seq_shard", True) \
            else P(ba, None, None)
        step = build_prefill_step(cfg, n_groups, act_spec=act)
        args = (inputs["params"], inputs["cache"], inputs["batch"])
        in_sh = (in_shardings["params"], in_shardings["cache"],
                 in_shardings["batch"])
        out_sh = (None, in_shardings["cache"])
    else:
        step = build_decode_step(cfg, n_groups)
        args = (inputs["params"], inputs["cache"], inputs["token"],
                inputs["index"])
        in_sh = (in_shardings["params"], in_shardings["cache"],
                 in_shardings["token"], in_shardings["index"])
        out_sh = (None, in_shardings["cache"])

    t0 = time.time()
    with mesh:
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_d = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes",
                              getattr(mem, "temp_size_in_bytes", 0)),
    }
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    roof = rl.build(arch, shape_name, mesh_name, chips, cost, mem_d, hlo)
    rec = roof.to_dict()
    rec.update({
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "n_groups": n_groups,
        "multi_pod": multi_pod,
        "status": "ok",
        "variant": variant,
        "overrides": ov,
        "chunk_size": cfg.chunk_size,
    })
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"compute={roof.compute_s*1e3:.2f}ms memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms dominant={roof.dominant} "
              f"mfu={roof.mfu:.3f} args/dev={mem_d['argument_bytes']/chips/1e9:.2f}GB "
              f"temp/dev={mem_d['temp_bytes']/chips/1e9:.2f}GB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        suffix = "" if variant == "base" else f"__{variant}"
        fn = os.path.join(RESULTS_DIR,
                          f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ensure_dryrun_devices()
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape cell (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch in archs:
        shapes = [args.shape] if args.shape else \
            [s for s in LM_SHAPES if s not in SKIPS[arch]]
        for shape_name in shapes:
            if shape_name in SKIPS[arch]:
                print(f"[{arch} x {shape_name}] SKIP (per DESIGN.md)")
                continue
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                fn = os.path.join(RESULTS_DIR,
                                  f"{arch}__{shape_name}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(fn):
                    print(f"[{arch} x {shape_name} x {mesh_name}] cached")
                    continue
                try:
                    run_cell(arch, shape_name, multi_pod=mp)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_name, str(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f[:3])
        raise SystemExit(1)
    print("\nALL CELLS GREEN")


if __name__ == "__main__":
    main()
