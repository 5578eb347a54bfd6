"""The comparison that decides ``correct`` for a served model.

Once the window has closed, the requests it served a token to (all of them,
or above ``check["requests"]`` a sample drawn from the seed with the longest
in it) are run through the plain reference: one forward over each prompt
with its served tokens (teacher forcing keeps every position comparable
after a near-tie). The number compared is the gap by which a served token's
reference logit lies below the reference's best at that position: 0 where
the engine's greedy token is the reference's argmax. Limits and the
readings they were set from are in the cell's file under ``check`` and in
PERF.md.
"""
import numpy as np

from chipbench import reference, weights


def choose(requests, seed, check):
    """The sample: the longest request served (finished or, under backlog,
    cut off by the end of the window), then the others in an order drawn
    from the seed, up to ``check["requests"]``."""
    done = [q for q in requests if q["comparable"]]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0xC0])
    order = [done[i] for i in rng.permutation(len(done))]
    longest = max(done, key=lambda q: q["prompt_len"] + len(q["tokens"]))
    picks = [longest] + [q for q in order if q is not longest]
    return picks[:check["requests"]]


def served(cfg, seed, rows, check):
    """rows: [(prompt, served tokens), ...] -> the verdict, each number
    beside its limit. A row is padded on the right (causal: unseen) to a
    multiple of ``check["pad_multiple"]``, so the reference compiles for a
    few lengths, and its logits are taken at the served positions alone,
    ``check["max_tokens"]`` of them to a row."""
    if not rows:
        return {"correct": False, "why": "no served request to compare"}
    step, width = int(check["pad_multiple"]), int(check["max_tokens"])
    ids, keep = [], []
    for prompt, toks in rows:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        row = np.zeros(-(-len(seq) // step) * step, np.int32)
        row[:len(seq)] = seq
        ids.append(row)
        keep.append(np.minimum(len(prompt) - 1 + np.arange(width),
                               len(row) - 1))
    logits = reference.forward(cfg, ids, weights.make_top(seed, cfg),
                               lambda i: weights.make_layer(seed, i, cfg),
                               keep=keep)
    all_gaps, scale, by_request = [], 0.0, []
    for (prompt, toks), lg in zip(rows, logits):
        toks = np.asarray(toks[:width])
        lg = np.asarray(lg, np.float32)[:len(toks)]
        if not np.isfinite(lg).all():
            return {"correct": False, "why": "reference logits not finite"}
        g = lg.max(-1) - lg[np.arange(len(toks)), toks]
        all_gaps.append(g)
        scale = max(scale, float(np.abs(lg).max()))
        by_request.append([len(prompt), len(toks), int((g > 0).sum()),
                           float(g.max())])
    g = np.concatenate(all_gaps)
    numbers = {"widest_gap": float(g.max()), "mean_gap": float(g.mean()),
               "off_argmax_share": float((g > 0).mean())}
    limits = check["limits"]
    return {"correct": all(numbers[k] <= limits[k] for k in limits),
            "numbers": numbers, "limits": limits, "tokens_compared": len(g),
            "logit_scale": scale,
            "by_request_prompt_tokens_off_widest": by_request}
