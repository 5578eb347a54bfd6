"""Olmo-Hybrid on the normal serving path (ISSUE 35): the gated delta rule
against its token recurrence, the engine against the plain reference
(``chipbench/reference_olmo_hybrid.py``: logits, not tokens), a prefix hit
that needs a state snapshot, and what a model with recurrent layers is
refused.

Sizes: one period (three linear layers and a full one), hidden 64, the
published head ratios (d_v = 2 d_k, as many linear heads as attention
heads), float32, so that a tolerance says something about the arithmetic
and not about bfloat16."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_olmo_hybrid as ref
from chipbench.builders import olmo_hybrid as builder
from paddle_tpu.models import paged
from paddle_tpu.models.paged import RadixPrefixBlockManager
from paddle_tpu.observability import TRACER
from paddle_tpu.ops.pallas import gated_delta as G
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.types import Request

CFG = json.loads((Path(__file__).parents[1] / "chipbench" / "tests" / "cells"
                  / "configs" / "tiny-olmo-hybrid.json").read_text())
SEED = 3
BS = 4                      # block size of every engine here


@pytest.fixture(autouse=True)
def highest():
    # float32 matmuls as float32 on every backend: the tolerances below are
    # those of float32 sums in another order
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return builder.build(CFG, SEED).eval()


def reference_logits(seq):
    return np.asarray(ref.forward(
        CFG, [np.asarray(seq, np.int32)], ref.make_top(SEED, CFG),
        lambda i: ref.make_layer(SEED, i, CFG))[0])


# ------------------------------------- the chunked rule and the recurrence
def recurrence(q, k, v, g, beta, state, lens):
    """The rule, a token at a time, in float64."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    state = np.asarray(state, np.float64).copy()
    out = np.zeros(v.shape)
    for b in range(q.shape[0]):
        for h in range(q.shape[2]):
            s = state[b, h]
            for t in range(int(lens[b])):
                s = np.exp(g[b, t, h]) * s
                s = s + beta[b, t, h] * np.outer(
                    k[b, t, h], v[b, t, h] - k[b, t, h] @ s)
                out[b, t, h] = q[b, t, h] @ s
            state[b, h] = s
    return out, state


def rule_inputs(t, beta_range, repeat_keys, incoming, seed=0):
    rng = np.random.default_rng(seed)
    b, h, dk, dv = 2, 3, 8, 16
    k = rng.normal(size=(b, t, h, dk))
    if repeat_keys:          # keys that nearly repeat: where beta = 2 bites
        k = 0.1 * k + rng.normal(size=(b, 1, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(b, t, h, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    v = rng.normal(size=(b, t, h, dv))
    g = -rng.uniform(0, 0.3, size=(b, t, h))
    beta = rng.uniform(*beta_range, size=(b, t, h))
    state = (rng.normal(size=(b, h, dk, dv)) if incoming
             else np.zeros((b, h, dk, dv)))
    f = lambda x: jnp.asarray(x, jnp.float32)
    return [f(x) for x in (q, k, v, g, beta, state)]


FORMS = {"xla": lambda *a, **kw: G.gated_delta_chunk_xla(*a, **kw),
         "pallas": lambda *a, **kw: G.gated_delta_chunk_pallas(
             *a, interpret=True, **kw)}

# (tokens, chunk, beta's range, keys that repeat, an incoming state)
RULE_CASES = {
    "one-chunk": (16, 16, (0, 2), False, False),
    "ragged-last-chunk": (37, 8, (0, 2), False, False),
    "incoming-state": (64, 16, (0, 2), False, True),
    "kernel-chunk-64": (100, 64, (0, 2), False, True),
    "beta-near-2": (100, 64, (1.8, 2.0), False, True),
    "beta-near-2-keys-repeat": (128, 32, (1.8, 2.0), True, True),
}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", RULE_CASES)
def test_chunked_rule_is_the_token_recurrence(case, form):
    t, chunk, beta_range, repeat, incoming = RULE_CASES[case]
    args = rule_inputs(t, beta_range, repeat, incoming)
    if form == "pallas":     # the kernel multiplies q, k, v as bfloat16
        args[:3] = [x.astype(jnp.bfloat16).astype(jnp.float32)
                    for x in args[:3]]
    lens = np.array([t, t - 5])          # the second row ends mid-chunk
    want_o, want_s = recurrence(*args, lens)
    o, s = FORMS[form](*args, jnp.asarray(lens, jnp.int32), chunk=chunk)
    # the state is float32 sums in another order; keys that repeat under
    # beta 2 make the solve's terms larger than its answer
    tol = 2e-4 if repeat else 2e-5
    np.testing.assert_allclose(np.asarray(s), want_s, atol=tol)
    # the output: as exact for the jnp twin; the kernel multiplies what
    # only the output reads in bfloat16 (2^-9 a factor, summed over a chunk)
    o_tol = tol if form == "xla" else 0.06
    for b in range(2):
        np.testing.assert_allclose(np.asarray(o)[b, :lens[b]],
                                   want_o[b, :lens[b]], atol=o_tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_step_is_the_recurrence_and_leaves_a_resting_slot_alone(dtype):
    q, k, v, g, beta, state = rule_inputs(1, (0, 2), False, True, seed=1)
    q, k, v, g, beta = (jnp.concatenate([x, x[::-1]])[:, 0]
                        for x in (q, k, v, g, beta))
    state = jnp.concatenate([state, state[::-1]])
    active = np.array([True, False, True, True])
    # the mixer hands q, k, v over in the model's dtype: the step computes
    # in float32 whatever they come in
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    want_o, want_s = recurrence(*(x.astype(jnp.float32)[:, None]
                                  for x in (q, k, v)),
                                g[:, None], beta[:, None], state,
                                active.astype(int))
    o, s = G.gated_delta_step(q, k, v, g, beta, state, jnp.asarray(active))
    np.testing.assert_allclose(np.asarray(o)[active], want_o[active, 0],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(s)[1], np.asarray(state)[1])


# --------------------------------------------- the model and the reference
def test_dense_forward_is_the_reference(model):
    seq = np.random.default_rng(1).integers(1, 256, 41, dtype=np.int32)
    got = np.asarray(model(jnp.asarray(seq)[None])[0])
    want = reference_logits(seq)
    # float32 both, sums in another order (the chunked rule, fused qkv)
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_paged_prefill_then_decode_is_the_reference_full_forward(model):
    """Logits, not tokens: the whole-prompt program's last logit, then
    eight decode steps through the cache (the K/V of the full layer, the
    state of the linear ones), each against the reference's forward over
    the whole sequence so far. Teacher-forced with the reference's argmax."""
    rng = np.random.default_rng(2)
    seq = list(rng.integers(1, 256, 19, dtype=np.int32))
    cache = paged.PagedKVCache.init_for(model.cfg, 16, BS, 2, 8)
    assert len(cache.k_pools) == 1 and len(cache.states) == 3
    rows = np.full((2, 8), 16, np.int32)
    rows[0, :7] = np.arange(7)
    ids = np.zeros((2, 24), np.int32)
    ids[0, :19] = seq
    logits, cache = paged.llama_prefill_paged(
        model, jnp.asarray(ids), jnp.array([19, 0]), cache,
        jnp.array([0, 2]), jnp.asarray(rows))
    for _ in range(8):
        want = reference_logits(seq)[-1]
        # float32 both; the decode step reads a state eight tokens deep
        np.testing.assert_allclose(np.asarray(logits)[0], want, atol=5e-5)
        seq.append(int(np.argmax(want)))
        logits, cache = paged.llama_decode_step_paged(
            model, jnp.array([seq[-1], 0]), cache, jnp.array([True, False]))
    # the slot that never ran kept the zero state it was made with
    assert float(jnp.abs(cache.states[0][0][1]).max()) == 0.0


# ------------------------------------------------------ the serving engine
DOC = np.random.default_rng(7).integers(1, 256, 32, dtype=np.int32)


def tail(n, seed):
    return np.concatenate([DOC, np.random.default_rng(seed).integers(
        1, 256, n, dtype=np.int32)])


PROMPT = tail(9, 0)


def traced(fn):
    """-> the spans ``fn()`` recorded."""
    TRACER.clear()
    TRACER.enable()
    try:
        fn()
    finally:
        TRACER.disable()
    events = [e for e in TRACER.export()["traceEvents"] if e["ph"] == "X"]
    TRACER.clear()
    return events


def engine(model, **kw):
    opts = dict(num_slots=4, block_size=BS, max_prompt_len=16,
                max_seq_len=128, num_blocks=64, num_state_snapshots=4)
    eng = LLMEngine(model, **{**opts, **kw})
    eng.first_logits = []
    sample = eng.exe.sample_rows

    def recorded(logits, *a, **k):
        eng.first_logits.append(np.asarray(logits))
        return sample(logits, *a, **k)
    eng.exe.sample_rows = recorded
    return eng


def serve(eng, prompt, n=6):
    rid = eng.add_request(Request(prompt, max_new_tokens=n))
    eng.run()
    return list(eng.requests[rid].tokens)


def cold_whole(model):
    eng = engine(model, max_prompt_len=64)
    return eng, serve(eng, PROMPT)


def chunked(chunk):
    def path(model):
        eng = engine(model, max_prompt_len=chunk)
        return eng, serve(eng, PROMPT)
    return path


def snapshot_hit(model):
    """The document is seen, seen again (a snapshot is taken at the end of
    the K/V match) and then asked a third time: restored, not computed."""
    eng = engine(model)
    serve(eng, tail(7, 1))
    serve(eng, tail(11, 2))
    assert eng.mgr.cache_stats["snap_taken"] == 1
    toks = serve(eng, PROMPT)
    assert eng.mgr.cache_stats["snap_restored"] == 1
    assert eng.mgr.cache_stats["token_hits"] == 32
    return eng, toks


def preempted_and_replayed(model):
    eng = engine(model, preemption=True)
    rid = eng.add_request(Request(PROMPT, max_new_tokens=6))
    while len(eng.requests[rid].tokens) < 3:
        eng.step()
    assert eng._preempt()
    eng.run()
    assert eng.stats["preemptions"] == 1
    return eng, list(eng.requests[rid].tokens)


PATHS = {"cold-whole-prompt": cold_whole, "chunks-of-8": chunked(8),
         "chunks-of-16": chunked(16), "snapshot-hit": snapshot_hit,
         "preempted-and-replayed": preempted_and_replayed}


@pytest.fixture(scope="module")
def want():
    """The reference's greedy continuation of PROMPT and its logits at the
    last prompt position."""
    seq, first = list(PROMPT), None
    for _ in range(6):
        lg = reference_logits(seq)[-1]
        first = lg if first is None else first
        seq.append(int(np.argmax(lg)))
    return first, seq[len(PROMPT):]


@pytest.mark.parametrize("path", PATHS)
def test_every_path_to_a_first_token_gives_the_reference_logits(
        model, want, path):
    first, tokens = want
    eng, got = PATHS[path](model)
    assert got == tokens
    # the logits that chose PROMPT's first token, whichever program made
    # them and wherever its state came from (float32 sums, another order).
    # A replay's last sample is of the resume prompt: compared by tokens.
    if path != "preempted-and-replayed":
        np.testing.assert_allclose(eng.first_logits[-1][0], first, atol=5e-5)
    eng.assert_quiescent()
    assert eng.kv.reconcile()["ok"]


def test_a_match_past_its_deepest_snapshot_is_adopted_to_the_snapshot(model):
    eng = engine(model)
    serve(eng, tail(7, 1))
    serve(eng, tail(11, 2))                  # snapshot at 32, the document
    long = np.concatenate([tail(11, 2), [5, 6, 7, 8, 9]])
    kv = eng.mgr.match_prefix(long)
    assert kv.token_count > 40 and kv.snapshot[0] == 32
    usable = eng.kv.match(long)
    assert (usable.token_count, len(usable.blocks), usable.cow) == \
        (32, 32 // BS, None)
    assert usable.offered == kv.token_count
    events = traced(lambda: serve(eng, long))
    state = [e for e in events if e["name"] == "serving.state"]
    assert [(e["args"]["matched"], e["args"]["restored"]) for e in state] \
        == [(kv.token_count, 32)]
    # seen a second time past the snapshot: a deeper one was planned, and
    # taken at the end of the K/V match, block-aligned
    assert state[0]["args"]["taken"] == 1
    deeper = eng.mgr.match_prefix(np.concatenate([long, [1]])).snapshot
    assert deeper[0] == kv.token_count // BS * BS
    chunks = [e["args"] for e in events if e["name"] == "exe.prefill_chunk"]
    assert all(c["state_layers"] == 3 for c in chunks)
    assert sum(c["useful"] for c in chunks) == len(long) - 32
    decode = [e["args"] for e in events if e["name"] == "serving.decode"]
    assert decode and all(d["state_slots"] == d["slots"] and
                          d["cache_layers"] == 1 for d in decode)


def test_snapshot_eviction_leaves_the_ledger_and_quiescence_whole(model):
    eng = engine(model, num_state_snapshots=2)
    docs = [np.random.default_rng(40 + i).integers(1, 256, 16, dtype=np.int32)
            for i in range(3)]
    for i, d in enumerate(docs):
        for j in range(2):                   # seen twice: a snapshot each
            serve(eng, np.concatenate([d, [10 + i, 20 + j, 3]]), n=2)
            assert eng.kv.reconcile()["ok"], eng.kv.reconcile()["diffs"]
    stats = eng.mgr.cache_stats
    assert (stats["snap_taken"], stats["snap_evicted"]) == (3, 1)
    assert eng.kv.ledger.snapshots == (2, 2)
    assert "state_snapshots=2/2" in eng.kv.ledger.describe()
    # the first document's snapshot went: its next asker computes it all
    assert eng.kv.match(np.concatenate([docs[0], [1, 2]])).token_count == 0
    assert eng.kv.match(np.concatenate([docs[2], [1, 2]])).token_count == 16
    eng.assert_quiescent()
    counts = eng.kv.ledger.counts()
    assert sum(counts.values()) == eng.mgr.num_blocks


def test_a_request_that_leaves_mid_prefill_frees_its_reserved_entry(model):
    eng = engine(model, max_prompt_len=8)
    serve(eng, tail(7, 1))
    rid = eng.add_request(Request(tail(11, 2), max_new_tokens=4))
    eng.step()                               # admitted, one chunk in
    assert eng.requests[rid]._snapshot_plan is not None
    assert eng.mgr.snapshots_held() == 1
    assert eng.cancel(rid)
    assert eng.mgr.snapshots_held() == 0
    eng.assert_quiescent()
    assert eng.kv.reconcile()["ok"]


# -------------------------------------------------- the trie's bookkeeping
def test_a_split_hands_each_half_its_snapshots_and_eviction_drops_them():
    mgr = RadixPrefixBlockManager(16, 4)
    mgr.enable_snapshots(4)
    toks = np.arange(1, 17, dtype=np.int32)
    mgr.allocate(0, 16)
    mgr.commit_prefix(0, toks)
    for depth in (4, 12):
        idx, evicted = mgr.reserve_snapshot()
        assert not evicted and mgr.attach_snapshot(toks, depth, idx)
    # a branch at token 8 splits the node between the two snapshots
    other = np.concatenate([toks[:8], [99, 98, 97, 96]]).astype(np.int32)
    mgr.allocate(1, 12)
    mgr.commit_prefix(1, other)
    assert mgr.match_prefix(np.concatenate([other, [1]])).snapshot[0] == 4
    assert mgr.match_prefix(np.concatenate([toks, [1]])).snapshot[0] == 12
    assert mgr.ledger.reconcile(mgr)["ok"]
    # a position that is gone takes no snapshot, and the entry is free again
    idx, _ = mgr.reserve_snapshot()
    assert not mgr.attach_snapshot(np.arange(50, 66, dtype=np.int32), 8, idx)
    assert mgr.snapshots_held() == 2
    # the blocks under the deeper snapshot leave the trie: so does it
    mgr.free(0)
    mgr.free(1)
    while mgr._parked:
        mgr._free.append(mgr._evict_one())
    assert mgr.snapshots_held() == 0
    assert mgr.cache_stats["snap_dropped"] == 3
    assert mgr.ledger.reconcile(mgr)["ok"]


# ------------------------------------------------------------ the refusals
def _draft(model):
    return LLMEngine(model, draft_model=model)


def _verify(model):
    eng = LLMEngine(model, num_slots=2, block_size=BS, max_prompt_len=8)
    z = np.zeros((2, 8), np.int32)
    eng.exe.verify_chunk(z, np.zeros(2, np.int32), np.zeros(2, np.int32),
                         np.array([0, 1], np.int32),
                         np.zeros((2, eng.max_blocks_per_seq), np.int32))


def _beams(model):
    LLMEngine(model).add_request(Request(PROMPT[:8], num_beams=2,
                                         max_new_tokens=2))


def _handoff(model):
    eng = LLMEngine(model, num_slots=2, block_size=BS, max_prompt_len=16)
    rid = eng.add_request(Request(PROMPT[:8], max_new_tokens=4))
    eng.step()
    eng.extract_sequence(rid)


class _Store:
    capacity = 1


REFUSED = {
    "a draft model": (_draft, "a draft model"),
    "verify_chunk": (_verify, "verify_chunk"),
    "beam search": (_beams, "beam search"),
    "context parallelism": (lambda m: LLMEngine(m, cp=2), "cp > 1"),
    "the KV handoff": (_handoff, "KV handoff"),
    "multi-LoRA": (lambda m: LLMEngine(m, adapter_store=_Store()),
                   "multi-LoRA"),
    "async_depth": (lambda m: LLMEngine(m, async_depth=2), "async_depth"),
    "int8 K/V": (lambda m: LLMEngine(m, kv_dtype="int8"), "quantized K/V"),
}


@pytest.mark.parametrize("what", REFUSED)
def test_what_recurrent_layers_are_not_served_with_raises(model, what):
    attempt, message = REFUSED[what]
    with pytest.raises(NotImplementedError,
                       match="recurrent .linear-attention. layers.*"
                             + message):
        attempt(model)


def test_a_cache_built_for_other_layers_is_refused(model):
    cache = paged.PagedKVCache.init(4, 8, BS, 4, 16, 1, 4, jnp.float32)
    with pytest.raises(ValueError, match="init_for"):
        paged.llama_decode_step_paged(model, jnp.zeros((1,), jnp.int32),
                                      cache, jnp.ones((1,), bool))


# ------------------------------------------- a K/V-only model, as before
def test_a_kv_only_engine_knows_nothing_of_the_state_store():
    import paddle_tpu as pt
    from paddle_tpu.models.mistral import MistralConfig, MistralForCausalLM
    pt.seed(0)
    cfg = MistralConfig.tiny(sliding_window=None)
    eng = LLMEngine(MistralForCausalLM(cfg).eval(), num_slots=2,
                    block_size=BS, max_prompt_len=8, num_state_snapshots=4)
    assert not eng.stateful and not eng.kv.stateful
    assert eng.cache.states == () and eng.exe.snaps == ()
    assert eng.mgr.snap_capacity == 0 and eng.kv.ledger.snapshots == (0, 0)
    assert "state_layers" not in eng.exe.span_args
    p = np.arange(1, 20, dtype=np.int32)

    def both():
        for prompt in (p, np.concatenate([p, [3, 4]])):
            eng.add_request(Request(prompt, max_new_tokens=3))
            eng.run()
    events = traced(both)
    assert not [e for e in events if e["name"] == "serving.state"]
    args = [e["args"] for e in events
            if e["name"] in ("serving.decode", "exe.decode_tick",
                             "exe.prefill", "exe.prefill_chunk")]
    assert args and not any(
        k in a for a in args for k in ("state_slots", "state_layers",
                                       "ctx_tokens"))
    # the K/V match is adopted whole, copy-on-write tail and all
    assert eng.mgr.cache_stats["token_hits"] == 19
    assert eng.kv.match(p).snapshot is None
