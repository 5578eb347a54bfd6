"""The comparison that decides ``correct`` for a served Trinity model: what
``correct.served`` compares, with this family's reference and weights in
the place of the LLaMA-shaped ones (``correct.py`` imports those by name;
PERF.md, section 7 (b)).

The gaps by the same arithmetic (``correct_ouro.verdict`` and ``rows_for``,
imported): one teacher-forced reference forward over each compared
request's prompt and served tokens, what the timed path produced (prefill
in chunks, then decode through the two block spaces) against the reference
computed in blocks, and at every served position the gap by which the
served token's reference logit lies below the reference's best. The
compared requests include the longest the window served (``choose``), a
prompt several windows long, so that a window layer that reads everything
and a full layer that is rotated both move the numbers.

The router is discontinuous, as Kimi-K2's: a near-tie between a token's
8th and 9th biased score can fall the other way in bfloat16, so the
precision is held by ``off_argmax_share`` and ``mean_gap`` is given room
above that tail (``correct_kimi_k2.py``); each limit from chip readings
(the cell's ``check_notes``; PERF.md, section 6, PR 44).
"""
import numpy as np

from chipbench import reference_trinity as reference
from chipbench.correct import choose  # noqa: F401  (the driver's import)
from chipbench.correct_ouro import rows_for, verdict


def served(cfg, seed, rows, check):
    """rows: [(prompt, served tokens), ...] -> the verdict, each number
    beside its limit."""
    if not rows:
        return {"correct": False, "why": "no served request to compare"}
    ids, keep = rows_for(rows, check)
    # the padding on the right is never seen by a kept position (causal),
    # but it is routed: a run of one token id would send hundreds of alike
    # tokens to one expert, past the bound the reference gathers an
    # expert's tokens to (``expert_cap``). The row's own tokens again are
    # as varied as the row.
    for row, (prompt, toks) in zip(ids, rows):
        n = len(prompt) + len(toks) - 1
        row[n:] = np.resize(row[:n], len(row) - n)
    logits = reference.forward(
        cfg, ids, reference.make_top(seed, cfg),
        lambda i: reference.make_layer(seed, i, cfg), keep=keep)
    return verdict(rows, logits, check)
