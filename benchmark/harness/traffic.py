"""One general generator for every job and traffic mix.  A mix is a data
file of parameters (``traffic/<name>.json``); everything drawn is drawn from
``--seed``, so the same seed gives the same inputs.

Copied in idea, not in code, from ``tools/serve_bench.py`` (its mixes arrive
per engine step on a step clock; these arrive on the wall clock).
"""
from statistics import NormalDist

import numpy as np


def _lengths(spec, rng, n):
    """``n`` integer lengths from a length spec: ``uniform`` over
    [min, max], ``lognormal`` with ``median`` and ``sigma`` clipped to
    [min, max], or ``fixed`` at ``value``.

    With ``"stratified": B`` the lengths come in blocks of ``B`` requests,
    each block holding ``B`` evenly spaced quantiles of the distribution
    (shifted by one uniform draw per block) in an order drawn from the seed,
    instead of independent draws: any stretch of a few blocks then carries
    the same amount of work and the same tail under every seed, which
    differ in order and pairing.  That is what lets a run of some tens of
    requests repeat within a few percent."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["min"]), int(spec["max"])
    block = int(spec.get("stratified", 0))
    if block:
        blocks = -(-n // block)
        u = ((rng.permuted(np.tile(np.arange(block), (blocks, 1)), axis=1)
              + rng.uniform(size=(blocks, 1))) / block).reshape(-1)[:n]
    else:
        u = rng.uniform(size=n)
    if dist == "uniform":
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi) \
            .astype(np.int64)
    if dist == "lognormal":
        z = np.array(list(map(NormalDist().inv_cdf, u)))
        draw = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(draw), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def _due_times(arrivals, rng, horizon_s):
    """Due times in seconds from the start of the load, ascending, all
    before ``horizon_s``."""
    process = arrivals["process"]
    if process == "backlog":
        return np.zeros(int(arrivals["requests"]))
    if process == "poisson":
        rate = float(arrivals["rate_per_s"])
        # more gaps than the horizon can hold, then cut: one draw, no loop
        n = int(rate * horizon_s * 1.5 + 50)
        due = np.cumsum(rng.exponential(1.0 / rate, size=n))
        return due[due < horizon_s]
    if process == "paced":
        # a Poisson process of that rate, stratified: each interval of
        # 1/rate seconds gets exactly one arrival, at a uniform place in it
        gap = 1.0 / float(arrivals["rate_per_s"])
        n = int(horizon_s / gap)
        return (np.arange(n) + rng.uniform(size=n)) * gap
    if process == "bursts":
        starts = np.arange(0.0, horizon_s, float(arrivals["every_s"]))
        return np.repeat(starts, int(arrivals["burst_size"]))
    raise ValueError(f"unknown arrival process {process!r}")


def requests(traffic, vocab_size, seed, horizon_s):
    """The requests of a serving mix: a dict of ``due`` (seconds from the
    start of the load), ``prompts`` (int32 arrays) and ``new_tokens``.

    ``shared_prefix`` (optional): ``{"tokens": n, "sessions": k}`` makes
    every prompt begin with one of ``k`` seeded prefixes of ``n`` tokens,
    and ``prompt_len`` then counts the tokens after it.

    ``lengths_seed`` (optional, an integer): the lengths are drawn from
    THAT seed and not from the run's, so every run of the mix serves the
    same lengths in the same order, and the run's seed draws the token ids
    (and the weights).  For a backlog that a window does not empty: which
    requests the window takes up is then the mix's and not the seed's, where
    a seed-drawn order moved the rate by 1.7 % from seed to seed (PERF.md
    section 6, PR 45).  A mix that does not state it draws as it always
    did."""
    rng = np.random.default_rng(seed)
    due = _due_times(traffic["arrivals"], rng, horizon_s)
    n = len(due)
    lengths_rng = np.random.default_rng(int(traffic["lengths_seed"])) \
        if "lengths_seed" in traffic else rng
    prompt_len = _lengths(traffic["prompt_len"], lengths_rng, n)
    new_tokens = _lengths(traffic["new_tokens"], lengths_rng, n)
    prompts = [rng.integers(0, vocab_size, k).astype(np.int32)
               for k in prompt_len]
    shared = traffic.get("shared_prefix")
    if shared:
        prefixes = rng.integers(0, vocab_size, (int(shared["sessions"]),
                                                int(shared["tokens"])))
        which = rng.integers(0, len(prefixes), size=n)
        prompts = [np.concatenate([prefixes[w], p]).astype(np.int32)
                   for w, p in zip(which, prompts)]
    return {"due": due, "prompts": prompts, "new_tokens": new_tokens}


def train_batches(traffic, vocab_size, seed, chips):
    """The distinct batches a training job cycles through, each
    ``(accumulation, rows, seq_len)`` of seeded token ids held in host
    memory (so the transfer to the device is part of every step), and the
    tokens in one."""
    rng = np.random.default_rng(seed)
    gas = int(traffic["gradient_accumulation"])
    rows = int(traffic["micro_batch_per_chip"]) * chips
    seq = int(traffic["seq_len"])
    batches = []
    for _ in range(int(traffic["distinct_batches"])):
        ids = rng.integers(0, vocab_size, (gas, rows, seq)).astype(np.int32)
        batches.append({"input_ids": ids, "labels": ids.copy()})
    return batches, gas * rows * seq
