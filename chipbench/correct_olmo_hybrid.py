"""The comparison that decides ``correct`` for a served Olmo-Hybrid model:
what ``correct.served`` compares, with the hybrid's reference and weights in
the place of the LLaMA-shaped ones (``correct.py`` imports those by name;
PERF.md, section 7 (b)), and one number more, of the recurrent state.

The gaps by the same arithmetic (``correct_ouro.verdict`` and ``rows_for``,
imported): one teacher-forced reference forward over each compared
request's prompt and served tokens, and at every served position the gap by
which the served token's reference logit lies below the reference's best.

``state_gap``: the gaps cannot see the precision of the recurrent state (a
state kept in bfloat16, even in float8, moves the argmax logits less than
bfloat16 activations through 16 layers do: PERF.md, section 6, PR 35), so
the state itself is compared. A request the window's end cut off still sits
in its slot, its state as the programs left it after every prompt token and
every decode tick so far. The driver reads the states of ``check["live"]``
such slots (those with the most ticks behind them), and the reference's
token-by-token recurrence over the same tokens gives what they should be.
A head's gap is ``|S_served - S_ref| / |S_ref|`` (Frobenius). The number is
taken in THE FIRST LINEAR LAYER: its inputs are the embedding's rows, which
both sides hold exactly, so what differs there is the rounding of one
layer's q, k, v and the state's own arithmetic and store; every deeper
layer's state inherits the bfloat16 rounding of the residual stream below
it, 1-2% of its norm, which drowns a store's 0.1% a step. All the linear
layers share one store (``PagedKVCache.states``, one dtype) and one kernel.
``state_gap`` is the worst head of the worst compared slot; the verdict
also notes each layer's worst head, unbounded.

``choose`` differs from ``correct.choose``: the reference runs the delta
rule a token at a time, so a cell compares a dozen requests and not all,
and the dozen has to hold what the cell is for: the longest request, the
live ones whose state is compared, then requests that began behind a shared
document (whose state came from a snapshot or was rebuilt) up to
``check["shared"]`` of them, those with the fewest tokens of their own
first, then the others in an order drawn from the seed.
"""
import numpy as np

from chipbench import reference_olmo_hybrid as reference
from chipbench.correct_ouro import rows_for, verdict


def choose(requests, seed, check, live=()):
    """``live``: indices (``q["index"]``) of the requests still in a slot
    at the window's end. The ``check["live"]`` of them with the most served
    tokens are compared, state and all."""
    done = [q for q in requests if q["comparable"]]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0xC0])
    order = [done[i] for i in rng.permutation(len(done))]
    longest = max(done, key=lambda q: q["prompt_len"] + len(q["tokens"]))
    picks = [longest] + sorted(
        (q for q in done if q["index"] in live and q is not longest),
        key=lambda q: -len(q["tokens"]))[:int(check.get("live", 0))]
    chosen = lambda q: any(q is p for p in picks)
    # behind a document, the shortest prompts first: the fewer tokens of
    # its own a request has, the more its answer leans on the state the
    # snapshot gave it (behind thousands of its own the decays have
    # forgotten the document, and a stale snapshot with it)
    picks += sorted((q for q in order if q["shared"] >= 0 and not chosen(q)),
                    key=lambda q: q["prompt_len"])[
                        :int(check.get("shared", 0))]
    picks += [q for q in order if not chosen(q)]
    return picks[:check["requests"]]


def state_gaps(served, ref):
    """served: a linear layer's state a slot holds, [H, d_k, d_v] (the
    program's layout), for each linear layer; ref: the reference's, [H,
    d_v, d_k] -> [layers, H], each head's distance over its norm."""
    out = []
    for s, r in zip(served, ref):
        s = np.asarray(s, np.float32)
        r = np.asarray(r, np.float32).transpose(0, 2, 1)
        out.append(np.sqrt(((s - r) ** 2).sum((1, 2))
                           / np.maximum((r ** 2).sum((1, 2)), 1e-30)))
    return np.stack(out)


def served(cfg, seed, rows, check, states=None):
    """rows: [(prompt, served tokens), ...]; states: {row: (n, [a linear
    layer's state after the row's first n tokens, as the slot holds it,
    ...])} -> the verdict, each number beside its limit."""
    if not rows:
        return {"correct": False, "why": "no served request to compare"}
    needs_state = "state_gap" in check["limits"]
    if needs_state and not states:
        return {"correct": False, "why": "no live slot's state to compare"}
    ids, keep = rows_for(rows, check)
    out = reference.forward(
        cfg, ids, reference.make_top(seed, cfg),
        lambda i: reference.make_layer(seed, i, cfg), keep=keep,
        state_at={k: n for k, (n, _) in (states or {}).items()})
    if not states:
        return verdict(rows, out, check)
    logits, ref_states = out
    gaps = {k: state_gaps(states[k][1], ref_states[k]) for k in states}
    if not all(np.isfinite(g).all() for g in gaps.values()):
        return {"correct": False, "why": "a served state is not finite"}
    extra = {"state_gap": max(float(g[0].max()) for g in gaps.values())}
    res = verdict(rows, logits, dict(check, limits={
        k: v for k, v in check["limits"].items() if k not in extra}))
    if "numbers" not in res:
        return res
    res["numbers"].update(extra)
    res["limits"] = check["limits"]
    res["correct"] = all(res["numbers"][k] <= v
                         for k, v in check["limits"].items())
    short = lambda xs: [round(float(x), 6) for x in xs]
    res["state_by_row"] = [
        {"row": k, "tokens": int(states[k][0]), "served": len(rows[k][1]),
         "worst_head_a_layer": short(g.max(1)),
         "mean_head_a_layer": short(g.mean(1)),
         "first_layer_heads": short(g[0])} for k, g in gaps.items()]
    return res
