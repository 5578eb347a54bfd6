"""The comparison that decides ``correct`` for a training cell: the
program's first steps against the plain reference's (``reference.train``),
number by number, each beside its limit (the cell's ``check.limits``).

- ``loss_gap``: the widest |program - reference| / reference over the steps;
- ``grad_norm_gap``: the first gradient as the optimizer gets it (clipped),
  read from its first moment after one step, by the worst weight matrix: the
  gap between the two norms over the reference's norm of that leaf or of the
  median leaf, whichever is larger (some gradients are all but zero);
- ``gain_grad_norm_gap``: the same by the worst norm gain. Apart, because a
  gain's bf16 gradient is a sum over every token and reads five to ten times
  a matrix's gap in a sound run, which hid the lower precision (PERF.md);
- ``delta_norm_gap``: the same, over all leaves, for the norm of each
  leaf's change after the steps;
- ``loss_not_finite``: steps of the whole run whose loss was not finite.
"""
import numpy as np

GAINS = ("ln_attn", "ln_mlp", "norm")      # the 1-D leaves, as published


def group_norms(ref: dict, groups: dict) -> dict:
    """The reference's per-tensor norms folded into the program's leaves:
    a fused leaf's norm is the root of its parts' squares."""
    out = {}
    for leaf, value in ref.items():
        if "." not in leaf:
            out[leaf] = out.get(leaf, 0.0) + value ** 2
            continue
        layer, tensor = leaf.split(".")
        group = next(g for g, names in groups.items() if tensor in names)
        key = f"{layer}.{group}"
        out[key] = out.get(key, 0.0) + value ** 2
    return {k: v ** 0.5 for k, v in out.items()}


def leaf_gaps(program: dict, ref: dict) -> dict:
    floor = float(np.median(list(ref.values())))
    return {k: abs(program[k] - ref[k]) / max(ref[k], floor) for k in ref}


def trained(program: dict, ref: dict, groups: dict, limits: dict) -> dict:
    steps = len(ref["loss"])
    numbers = {"loss_gap": max(abs(p - r) / r for p, r in
                               zip(program["loss"][:steps], ref["loss"]))}
    where, by_leaf = {}, {}

    def worst(number, gaps):
        where[number] = max(gaps, key=gaps.get)
        numbers[number] = gaps[where[number]]

    for name in ("grad_norm", "delta_norm"):
        gaps = leaf_gaps(program[name], group_norms(ref[name], groups))
        by_leaf[name] = {k: float(f"{v:.3g}") for k, v in gaps.items()}
        if name == "delta_norm":
            worst("delta_norm_gap", gaps)
            continue
        gain = lambda leaf: leaf.rpartition(".")[2] in GAINS
        worst("grad_norm_gap", {k: v for k, v in gaps.items() if not gain(k)})
        worst("gain_grad_norm_gap", {k: v for k, v in gaps.items() if gain(k)})
    numbers["loss_not_finite"] = float(program["not_finite"])
    return {"correct": all(numbers[k] <= limits[k] for k in limits),
            "numbers": numbers, "limits": limits, "worst_leaf": where,
            "leaf_gaps": by_leaf,
            "loss_program": program["loss"][:steps], "loss_reference": ref["loss"]}
