"""``sessions``: the one general generator of serving traffic.

A mix is a data file of parameters (``chipbench/traffic/<mix>.json``); this
file turns ``(seed, parameters)`` into a list of requests and nothing else.
Shape after the Azure conversation trace (heavy-tailed lengths, prompts much
longer than answers) and Mooncake (a share of prompts begin with one of a
few system prompts, popularity Zipf).

Lengths, sharing and arrival gaps are the quantile grid of each
distribution, in one fixed order: a trace that every run replays. The seed
draws the token values (and, elsewhere, the weights) and nothing else, so the
engine does the same work under every seed. With the order drawn from the
seed, three seeds of the backlog cell read 26.2, 31.0 and 28.9 tokens/s on
the chip while one seed read 26.211 and 26.217 twice (PERF.md): a window
holds some twenty requests, and which long prompt comes first decided it.
The order is stratified: every block of ``block`` consecutive requests holds
one value from each of ``block`` strata of every quantity, so any stretch of
the trace carries nearly the same work.

Parameters::

    n             requests generated (enough for lead-in, window and drain)
    block         stratification block (divides n)
    prompt_len    {"median", "sigma", "min", "max"}   lognormal, clipped
    output_len    {"median", "sigma", "min", "max"}   lognormal, clipped
    shared        {"share", "prompts", "tokens", "zipf_alpha", "min_own"}
    rate          requests/s of an open loop with exponential gaps, or the
                  string "backlog": every request due at t = 0
    in_flight     optional: the first ``in_flight`` requests stand for those
                  a running engine holds when the trace begins, each at
                  another point of its answer: request k of them has
                  (k + 0.5) / in_flight of its output still to come, in a
                  fixed shuffled order, so slots free up all through a window
                  and not all at once
"""
from statistics import NormalDist

import numpy as np


def _grid(n):
    return (np.arange(n) + 0.5) / n


def _lognormal_grid(n, p):
    z = np.array([NormalDist().inv_cdf(q) for q in _grid(n)])
    v = p["median"] * np.exp(p["sigma"] * z)
    return np.clip(np.rint(v), p["min"], p["max"]).astype(np.int64)


def _gap_grid(n, rate):
    return -np.log1p(-_grid(n)) / rate


def _stratified(values, block, rng):
    """The sorted values dealt into ``block`` strata; each block of the
    result takes one from every stratum, in an order drawn from ``rng``."""
    values = np.sort(np.asarray(values))
    groups = len(values) // block
    strata = values.reshape(block, groups)
    strata = np.stack([rng.permutation(row) for row in strata])
    out = strata.T.copy()                   # [groups, block]
    for row in out:
        rng.shuffle(row)
    return out.reshape(-1)


def requests(seed: int, params: dict, vocab_size: int) -> list:
    """-> [{"due": seconds from the start of the traffic, "prompt":
    int32 array, "max_new_tokens": int, "shared": index or -1}, ...] in
    order of ``due``. A pure function of its arguments."""
    n, block = int(params["n"]), int(params["block"])
    if n % block:
        raise ValueError("n must be a multiple of block")
    rng = np.random.default_rng([0, 0x5E55])
    tokens = np.random.default_rng([int(seed), 0x70C])
    plen = _stratified(_lognormal_grid(n, params["prompt_len"]), block, rng)
    olen = _stratified(_lognormal_grid(n, params["output_len"]), block, rng)

    sh = params["shared"]
    n_sys, sys_len = int(sh["prompts"]), int(sh["tokens"])
    per_block = int(round(sh["share"] * block))
    # which system prompt each sharing request begins with: the Zipf
    # quantile grid over all sharing requests, dealt out in seeded order
    w = 1.0 / np.arange(1, n_sys + 1) ** sh["zipf_alpha"]
    cdf = np.cumsum(w / w.sum())
    n_shared = per_block * (n // block)
    which = rng.permutation(np.searchsorted(cdf, _grid(n_shared)))
    shared = np.full(n, -1, np.int64)
    for b in range(n // block):
        at = b * block + rng.permutation(block)[:per_block]
        shared[at] = which[b * per_block:(b + 1) * per_block]

    if params["rate"] == "backlog":
        due = np.zeros(n)
    else:
        gaps = _stratified(_gap_grid(n, float(params["rate"])), block, rng)
        due = np.cumsum(gaps) - gaps[0]

    held = int(params.get("in_flight", 0))
    left = (rng.permutation(held) + 0.5) / max(held, 1)
    olen[:held] = np.maximum(1, np.rint(olen[:held] * left))

    system = tokens.integers(1, vocab_size, (n_sys, sys_len), dtype=np.int32)
    out = []
    for i in range(n):
        length = int(plen[i])
        if shared[i] >= 0:
            length = max(length, sys_len + int(sh["min_own"]))
            own = tokens.integers(1, vocab_size, length - sys_len,
                                  dtype=np.int32)
            prompt = np.concatenate([system[shared[i]], own])
        else:
            prompt = tokens.integers(1, vocab_size, length, dtype=np.int32)
        out.append({"due": float(due[i]), "prompt": prompt,
                    "max_new_tokens": int(olen[i]), "shared": int(shared[i])})
    return out
