"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

Sizes and arrival gaps are the same for every seed: lengths are the
stratified quantiles of the mix's clipped lognormal, and gaps those of an
exponential at the mix's rate, each scattered by the mix's fixed
``order_seed``. The run's ``--seed`` draws the token ids and shuffles which
request comes when within consecutive blocks of ``shuffle_block``, so two
seeds serve the same work in another order.

An open loop's schedule starts ``warm_s`` seconds before the window, so
the window opens on a server in its steady state; a closed loop's first
requests can join as if already in flight (``warm_start: residual``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Req:
    prompt: np.ndarray
    max_new: int
    due: float = 0.0            # seconds on the schedule's clock
    # filled in while serving
    consumed: int = 0           # tokens its cache row holds
    admitted: float = math.nan
    finished: float = math.nan
    token_times: list = field(default_factory=list)
    handle: object = None


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(raw), spec["min"], spec["max"]).astype(np.int64)


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def requests(mix: dict, seed: int, vocab: int) -> list[Req]:
    """The mix's request pool, in the order they are sent."""
    n = mix["pool"]
    fixed = rng_for(mix["order_seed"])
    plen = fixed.permutation(lognormal_lengths(mix["prompt_tokens"], n))
    olen = fixed.permutation(lognormal_lengths(mix["output_tokens"], n))
    order = np.arange(n)
    rng = rng_for(seed, 1)
    blk = mix["shuffle_block"]
    for s in range(0, n, blk):
        order[s:s + blk] = rng.permutation(order[s:s + blk])
    due = np.zeros(n)
    if mix["kind"] == "open_loop":
        gaps = fixed.permutation(-np.log1p(-_quantiles(n)) / mix["rate_rps"])
        due = np.cumsum(gaps) - gaps[0] - mix.get("warm_s", 0.0)
    outs = olen[order]
    if mix.get("warm_start") == "residual":
        # the first clients' requests join in flight: each keeps a share
        # of its output spread evenly over (0, 1), as if it had started
        # earlier, so the window opens on staggered ends, not one wave
        k = mix["clients"]
        share = fixed.permutation(_quantiles(k))
        outs[:k] = np.maximum(1, np.round(outs[:k] * share))
    ids = rng_for(seed, 2)
    return [Req(ids.integers(0, vocab, int(plen[j])).astype(np.int32),
                int(outs[i]), float(due[i]))
            for i, j in enumerate(order)]


def train_batch(mix: dict, seed: int, step: int, vocab: int) -> dict:
    """Rows of ``seq_len + 1`` Zipf-distributed token ids for one step
    (the marginal of the program's ``SyntheticLM``), as tokens/targets."""
    rng = rng_for(seed, 3, step)
    ranks = rng.zipf(mix["zipf_a"], size=(mix["batch"], mix["seq_len"] + 1))
    raw = (ranks % vocab).astype(np.int32)
    return {"tokens": raw[:, :-1], "targets": raw[:, 1:]}
