"""The comparison that decides ``correct`` for a served Kimi-K2 model: what
``correct.served`` compares, with this family's reference and weights in
the place of the LLaMA-shaped ones (``correct.py`` imports those by name;
PERF.md, section 7 (b)).

The gaps by the same arithmetic (``correct_ouro.verdict`` and ``rows_for``,
imported): one teacher-forced reference forward over each compared
request's prompt and served tokens, on the same share of the experts, and
at every served position the gap by which the served token's reference
logit lies below the reference's best.

The router is discontinuous: a near-tie between a token's 8th and 9th
biased score can fall the other way in bfloat16, and where the expert
concerned is held here that token's logits move by far more than rounding
moves them (a gap of 0.2-0.8 where a sound token's is 0 or a few
thousandths). A few such tokens a run give ``mean_gap`` a tail, so a cell of
this family holds the precision with ``off_argmax_share`` (the share of
served tokens that are not the reference's argmax: near-ties in the LOGITS,
which rounding decides, and which a lower precision doubles), gives
``mean_gap`` room above the tail, and bounds ``widest_gap`` so that a fault
in a few tokens cannot hide behind the mean; each from chip readings
(PERF.md, section 6, PR 41). The timed programs hand out counts of what
they routed, not the choices (PERF.md, section 7), so the choices
themselves are not compared.
"""
import numpy as np

from chipbench import reference_kimi_k2 as reference
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
