"""The one generator every traffic mix is read by.

A mix is a JSON file of parameters under ``traffic/``. Its ``kind`` says
what the parameters describe:

- ``conv_passes``: ``layers``, the configuration's layers called once each,
  in order, per pass. Operands come from the seed.
- ``offline_jobs``: jobs of ``job_requests`` requests, each job one list
  handed to the server at once. ``prompt`` and ``output`` give the length
  distributions (``lognormal`` with ``median`` and ``sigma``, or
  ``uniform``), clipped to ``[min, max]``; ``max_len`` is the cache length
  the server is built for; ``check_requests`` how many finished requests
  the correctness check reads.

Operands and weights come from ``seed_key(seed)``. Every job of every seed
holds the same lengths in the same order: the lengths are the
distribution's quantiles at (i + 1/2) / n, shuffled by the job's index
alone, and the seed draws the token ids. So a seed changes the content of
the work, never its amount or its schedule: on the paged engine the order
of the lengths decides how the slots fill and how long a job's tail
drains, which moved a decode job's time by up to 3% from seed to seed
when the seed shuffled them.
"""

from __future__ import annotations

import math
import statistics
from typing import List, Tuple

import numpy as np

def seed_key(seed: int):
    """A JAX key from any non-negative seed, also past 32 bits: operands and
    weights are drawn from it."""
    import jax

    hi, lo = divmod(int(seed), 2 ** 31)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def quantile_lengths(dist: dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles (i + 1/2) / n of ``dist``, clipped."""
    lo, hi = int(dist["min"]), int(dist["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        z = statistics.NormalDist()
        mu, s = math.log(dist["median"]), float(dist["sigma"])
        raw = [math.exp(mu + s * z.inv_cdf(q)) for q in qs]
    elif dist["dist"] == "uniform":
        raw = [lo + q * (hi - lo + 1) - 0.5 for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [min(hi, max(lo, int(round(r)))) for r in raw]


def job(mix: dict, seed: int, index: int, vocab: int
        ) -> List[Tuple[np.ndarray, int]]:
    """Job ``index`` of ``seed``: (prompt token ids, tokens to generate) for
    each request, in the order they are handed to the server."""
    n = int(mix["job_requests"])
    order = np.random.default_rng(index)
    prompts = order.permutation(quantile_lengths(mix["prompt"], n))
    outputs = order.permutation(quantile_lengths(mix["output"], n))
    rng = np.random.default_rng([seed, index])
    return [(rng.integers(0, vocab, size=int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]


def longest(mix: dict) -> Tuple[int, int]:
    """(longest prompt, longest output) any job of the mix holds."""
    n = int(mix["job_requests"])
    return (max(quantile_lengths(mix["prompt"], n)),
            max(quantile_lengths(mix["output"], n)))


def shortest_prompt(mix: dict) -> int:
    return min(quantile_lengths(mix["prompt"], int(mix["job_requests"])))
