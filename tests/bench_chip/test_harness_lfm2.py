"""The LFM2-MoE serving cell driven end to end on the CPU at a tiny size:
correct as it stands, not correct with a served token altered or with the
fp8 control in the program's place; and its two expert readers on the
engine's counters and a synthetic trace."""

import json
import os
import shutil
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import trace as tr
from benchmarks.chip.peaks import peaks_for
from repro.serving.engine import ServeStats

from conftest import ROOT, run_cell

SEED = 2**31 + 11
LFM2_DIR = os.path.join("benchmarks", "chip", "lfm2")
COMMITTED = json.load(open(os.path.join(ROOT, LFM2_DIR, "configs",
                                        "lfm2-8b-a1b.json")))
# Six layers in the published mix of kinds, two dense; 8 experts routed,
# this chip holding experts 2-3. A model 32 times narrower has its own
# limits: on the CPU at this size the mean gap reads 7.6e-6 to 9.0e-4, the
# fp8 control 0.0105-0.0318; the widest gap 0.0003-0.016, the control
# 0.075-0.21 (seeds 1, 2, 3, 5, 7, 2**31 + 9, 2**31 + 11, one job each).
TINY_LFM2 = dict(
    COMMITTED, name="tiny-lfm2", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=6, num_attention_heads=4,
    num_key_value_heads=2, vocab_size=512, num_experts=2,
    layer_types=["conv", "conv", "full_attention", "conv", "full_attention",
                 "conv"],
    deployment=dict(COMMITTED["deployment"], num_experts=8,
                    experts_held=[2, 2]),
    check={"mean_gap_limit": 0.004, "logit_gap_limit": 0.06})
TINY_JOBS = {
    "kind": "offline_jobs", "job_requests": 6, "slots": 4,
    "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.7, "min": 3,
               "max": 20},
    "output": {"dist": "uniform", "min": 2, "max": 9},
    "max_len": 32, "check_requests": 6,
}


@pytest.fixture
def tiny_lfm2_root(tmp_path):
    """A checkout-like tree with one tiny cell, ``tiny-lfm2.jobs``, whose
    files sit beside the committed LFM2 configuration."""
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "lfm2" / "configs" / "tiny-lfm2.json").write_text(
        json.dumps(TINY_LFM2))
    (bench / "lfm2" / "traffic" / "jobs.json").write_text(
        json.dumps(TINY_JOBS))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"] = [{"name": "tiny-lfm2", "source": "test",
                        "file": f"{LFM2_DIR}/configs/tiny-lfm2.json",
                        "reduced": ["num_experts"], "why": "test"}]
    spec["workloads"] = [{"name": "tiny-lfm2.jobs", "config": "tiny-lfm2",
                          "traffic": "jobs", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-lfm2.jobs"]
                              if "lfm2-8b-a1b.decode256" in m["workloads"]
                              else ["elsewhere"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path)


def control_greedy(config, seed, prompt, n, length):
    """``n`` tokens after ``prompt``, each the one the fp8 control ranks
    first, rows padded to ``length`` so that it compiles once."""
    from benchmarks.chip.reference import lfm2_moe as ref
    from benchmarks.chip.traffic import seed_key

    dims = ref.dims_of(config)
    glob = ref.global_weights(seed_key(seed), dims)
    embed = glob["embed"].astype(jnp.float32)
    norm = glob["final_norm"].astype(jnp.float32)
    seq = [int(t) for t in prompt]
    for _ in range(n):
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        x, _ = ref.forward_hidden(config, seed, tokens, fp8=True)
        _, _, pick = ref._head_stats(x[0], norm, embed,
                                     jnp.zeros(length, jnp.int32),
                                     eps=float(config["norm_eps"]), fp8=True)
        seq.append(int(pick[len(seq) - 1]))
    return np.asarray(seq[len(prompt):], np.int32)


def altered_serve(real):
    def serve(self, requests):
        real(self, requests)
        for r in requests:
            r.out_tokens[-1] = (r.out_tokens[-1] + 1) % self.cfg.vocab_size
        return requests
    return serve


def control_serve(real):
    def serve(self, requests):
        for r in requests:
            r.out_tokens = control_greedy(TINY_LFM2, SEED, r.prompt,
                                          r.max_new_tokens,
                                          TINY_JOBS["max_len"])
            r.finish_reason = "length"
        return requests
    return serve


@pytest.mark.parametrize("fault", [None, altered_serve, control_serve],
                         ids=["sound", "altered_token", "fp8_control"])
def test_lfm2_cell_correct_unless_faulted(tiny_lfm2_root, capsys,
                                          monkeypatch, fault):
    from repro.serving.engine import Engine

    if fault:
        monkeypatch.setattr(Engine, "serve", fault(Engine.serve))
    out = run_cell(tiny_lfm2_root, "tiny-lfm2.jobs", capsys, seed=SEED)
    assert out["correct"] is (fault is None)
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    assert out["checks"]["logit_gap"]["limit"] == 0.06
    assert out["checks"]["mean_gap"]["limit"] == 0.004
    assert out["checks"]["out_of_vocab"]["value"] == 0.0
    if fault is control_serve:  # the mean alone tells fp8 from bfloat16
        assert out["checks"]["mean_gap"]["value"] > 0.004


def test_the_cell_builds_the_deployment_share(tiny_lfm2_root):
    """The engine holds the configuration's share, pages its attention,
    buckets its prefills and serves the mix's slots."""
    from benchmarks.chip import spec

    cell = spec.resolve("tiny-lfm2.jobs", tiny_lfm2_root)
    cfg = cell.kind_module().model_config(cell.config)
    assert cfg.experts_held == (2, 2) and cfg.n_experts == 8
    assert cfg.expert_shards == 4 and cfg.expert_shard == 1
    assert cfg.pattern.count("attn") == 2 and cfg.n_dense_layers == 2
    system = cell.kind_module().System(cell, SEED, lambda m: None)
    system.setup()
    eng = system.engine
    assert eng.paged and eng.prefill_bucket == 16 and eng.batch_size == 4


def test_engine_in_float32_serves_the_reference_argmax(tiny_lfm2_root):
    """The engine (bucketed prefill, then paged decode with the conv state
    and the dropless experts) in float32, on the reference's weights: every
    served token is the float32 reference's best at its position, within
    5e-3 of a logit. The engine's caches (the KV pool and the conv state)
    hold bfloat16 whatever the compute type, which moves a logit by up to
    4.7e-4 here; a wrong conv state or expert moves it by 0.05 and more."""
    import dataclasses

    from benchmarks.chip import spec
    from benchmarks.chip.reference import lfm2_moe as ref
    from repro.serving.engine import Engine, Request

    import jax

    cell = spec.resolve("tiny-lfm2.jobs", tiny_lfm2_root)
    kind = cell.kind_module()
    cfg = kind.model_config(cell.config)
    params = kind.program_weights(cell.config, cfg, SEED)
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, 512, n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in ((3, 9), (17, 6), (9, 12), (20, 4), (5, 7))]
    Engine(cfg, params, max_len=32, batch_size=2).serve(reqs)
    out = ref.served_gaps(cell.config, SEED,
                          [(r.prompt, r.out_tokens) for r in reqs],
                          length=32)
    assert len(out["gap"]) == 38 and float(out["gap"].max()) < 5e-3


# ---------------------------------------------------------------------------
# The readers
# ---------------------------------------------------------------------------

MS = 1_000_000


def reader(name):
    from benchmarks.chip import spec

    return spec.load_module(f"{spec.HERE}/metrics/{name}.py").read


def gmm_op(name, start, end):
    hlo = (f"%{name} = bf16[1280,1792]{{1,0}} custom-call(%a, %b), "
           f"custom_call_target=\"tpu_custom_call\"")
    return tr.Event(hlo, start * MS, end * MS, "/device:TPU:0", tr.OPS_LINE,
                    custom_call=True)


def test_expert_readers_on_counters_and_a_synthetic_trace():
    """The counters of a prefill and a decode, beside a trace of two
    grouped-matmul calls and another kernel: the rows ratio is rows over
    held pairs, whatever the trace holds. The grouped matmuls' work
    (``work_lfm2.gmm_work``) is each row's gate, up and down, and each
    group's three weight matrices plus every row read and written once per
    matmul, in bfloat16."""
    from benchmarks.chip import work_lfm2
    from benchmarks.chip.reference import lfm2_moe

    stats = ServeStats(moe_routed={"prefill": 400, "decode": 1000},
                       moe_held={"prefill": 100, "decode": 250},
                       moe_rows={"prefill": 256, "decode": 384},
                       moe_groups={"prefill": 8, "decode": 8})
    system = types.SimpleNamespace(  # noqa: F841
        engine=types.SimpleNamespace(stats=stats))
    ops = [gmm_op("grouped_matmul.1", 0, 3),
           gmm_op("paged_decode_attention.2", 3, 8),
           gmm_op("grouped_matmul.3", 8, 11)]
    r = types.SimpleNamespace(
        trace=tr.Trace(ops, [], [], (0, 20 * MS)), info={},
        peaks=peaks_for("TPU v5 lite"),
        cell=types.SimpleNamespace(config=COMMITTED), say=lambda m: None)
    assert reader("gmm_rows_ratio.moe")(r) == pytest.approx(640 / 350)
    D, F = 2048, 1792
    assert work_lfm2.gmm_work(lfm2_moe.dims_of(COMMITTED), 384, 8) == (
        384 * 2 * 3 * D * F, 2 * (3 * 8 * D * F + 3 * 384 * (D + F)))


def test_expert_readers_find_nothing_without_counters():
    """A program with no expert layer (empty counters, no kernel): None."""
    system = types.SimpleNamespace(  # noqa: F841
        engine=types.SimpleNamespace(stats=ServeStats()))
    r = types.SimpleNamespace(
        trace=tr.Trace([], [], [], (0, MS)), info={},
        peaks=peaks_for("TPU v5 lite"), cell=None, say=lambda m: None)
    assert reader("gmm_rows_ratio.moe")(r) is None


def test_job_flops_count_held_expert_pairs():
    """Two requests' FLOPs: the non-expert work a token needs, the head per
    generated token, attention over its keys, and each held pair's gate,
    up and down."""
    from benchmarks.chip import work_lfm2
    from benchmarks.chip.reference import lfm2_moe

    dims = lfm2_moe.dims_of(COMMITTED)
    base = work_lfm2.job_flops(dims, [(10, 5), (3, 1)], 0)
    more = work_lfm2.job_flops(dims, [(10, 5), (3, 1)], 7)
    assert more - base == 7 * 2 * 3 * 2048 * 1792
    one = work_lfm2.job_flops(dims, [(1, 1)], 0)
    # one token through 24 layers, the head once, one key per attention
    assert one == (2 * sum(work_lfm2._non_expert_layer_params(dims, i)
                           for i in range(24))
                   + 2 * 2048 * 65536 + 4 * 6 * 32 * 64)
