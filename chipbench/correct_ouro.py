"""The comparison that decides ``correct`` for a served Ouro model: what
``correct.served`` compares, with the Ouro reference and weights in the
place of the LLaMA-shaped ones (``correct.py`` imports those by name).

The same numbers by the same arithmetic: one teacher-forced reference
forward over each served request's prompt and tokens, and at every served
position the gap by which the served token's reference logit lies below
the reference's best. ``choose`` is ``correct.choose`` itself. Until a
configuration can name its reference and weights modules (PERF.md, section
7), this file and ``drivers/serve_ouro.py`` are the two copies that let a
new family be compared without an edit to the accepted files;
``tests/test_ouro_cell.py`` feeds both verdicts the same logits and asserts
that they agree.
"""
import numpy as np

from chipbench import reference_ouro
from chipbench.correct import choose  # noqa: F401  (the driver's import)


def rows_for(rows, check):
    """-> (padded id rows, the positions kept of each): as ``correct.served``
    pads a row on the right (causal: unseen) to a multiple of
    ``check["pad_multiple"]`` and keeps ``check["max_tokens"]`` served
    positions."""
    step, width = int(check["pad_multiple"]), int(check["max_tokens"])
    ids, keep = [], []
    for prompt, toks in rows:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        row = np.zeros(-(-len(seq) // step) * step, np.int32)
        row[:len(seq)] = seq
        ids.append(row)
        keep.append(np.minimum(len(prompt) - 1 + np.arange(width),
                               len(row) - 1))
    return ids, keep


def verdict(rows, logits, check):
    """The numbers and the verdict from the reference's logits at the
    served positions, as ``correct.served`` computes them."""
    width = int(check["max_tokens"])
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


def served(cfg, seed, rows, check):
    """rows: [(prompt, served tokens), ...] -> the verdict, each number
    beside its limit."""
    if not rows:
        return {"correct": False, "why": "no served request to compare"}
    ids, keep = rows_for(rows, check)
    logits = reference_ouro.forward(
        cfg, ids, reference_ouro.make_top(seed, cfg),
        lambda i: reference_ouro.make_layer(seed, i, cfg), keep=keep)
    return verdict(rows, logits, check)
