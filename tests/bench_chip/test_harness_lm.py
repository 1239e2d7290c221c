"""An LM serving cell driven end to end on the CPU at a tiny size: correct
as it stands, not correct with a served token altered or with the fp8
control in the program's place."""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_JOBS, TINY_LM, control_readings, run_cell

SEED = 2**31 + 11


def control_greedy(config, seed, prompt, n, length):
    """``n`` tokens after ``prompt``, each the one the fp8 control ranks
    first: the reference in float8 decoding greedily, rows padded to
    ``length`` so that it compiles once."""
    from benchmarks.chip.reference import qwen2 as ref
    from benchmarks.chip.traffic import seed_key

    dims = ref.dims_of(config)
    frozen = tuple(sorted(dims.items()))
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    key = seed_key(seed)
    glob = ref.global_weights(key, dims)
    embed = glob["embed"].astype(jnp.float32)
    norm = glob["final_norm"].astype(jnp.float32)
    seq = [int(t) for t in prompt]
    for _ in range(n):
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        x = jnp.take(embed, jnp.asarray(tokens), axis=0)
        for layer in range(dims["num_hidden_layers"]):
            x = ref._layer_step(x, key, layer, dims=frozen, eps=eps,
                                theta=theta, fp8=True)
        _, _, pick = ref._head_stats(x[0], norm, embed,
                                     jnp.zeros(length, jnp.int32), eps=eps,
                                     fp8=True)
        seq.append(int(pick[len(seq) - 1]))
    return np.asarray(seq[len(prompt):], np.int32)


def altered_serve(real):
    """``Engine.serve`` with the last served token of each request altered."""
    def serve(self, requests):
        real(self, requests)
        for r in requests:
            r.out_tokens[-1] = (r.out_tokens[-1] + 1) % self.cfg.vocab_size
        return requests
    return serve


def control_serve(real):
    """``Engine.serve`` answering with the tokens the fp8 control ranks
    first: the control in the program's place."""
    def serve(self, requests):
        for r in requests:
            r.out_tokens = control_greedy(TINY_LM, SEED, r.prompt,
                                          r.max_new_tokens,
                                          TINY_JOBS["max_len"])
            r.finish_reason = "length"
        return requests
    return serve


@pytest.mark.parametrize("fault", [None, altered_serve, control_serve],
                         ids=["sound", "altered_token", "fp8_control"])
def test_lm_cell_correct_unless_faulted(tiny_root, capsys, monkeypatch,
                                        fault):
    """Run through the whole harness, a sound cell reads ``correct: true``;
    with a served token altered, or with the fp8 control serving, false."""
    from repro.serving.engine import Engine

    if fault:
        monkeypatch.setattr(Engine, "serve", fault(Engine.serve))
    out = run_cell(tiny_root, "tiny-lm.jobs", capsys, seed=SEED)
    assert out["correct"] is (fault is None)
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    gap = out["checks"]["logit_gap"]
    assert gap["limit"] == TINY_LM["check"]["logit_gap_limit"]
    assert (gap["value"] > gap["limit"]) is (fault is not None)
    assert out["failed"] == 0 and out["attempted"] > 0


def test_lm_control_is_not_correct(tiny_root):
    checks, control = control_readings(tiny_root, "tiny-lm.jobs")
    gap = dict((n, (v, lim)) for n, v, lim in checks)["logit_gap"]
    (cname, cvalue), = control
    assert cname == "logit_gap"
    assert gap[0] <= gap[1] < cvalue
