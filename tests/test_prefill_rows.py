"""The two prefill programs are sent the rows that carry a prompt (ISSUE 31):
``prefill_rows`` of them a call, ``ceil(live / prefill_rows)`` calls a tick.

The tiny engines here take ``max_prompt_len`` 256 so that the rule on shapes
gives one row a call, as it does in the benchmark's serving cells, and a tick
with more live rows than that sends several calls. The reference of every
served token is what the existing engine tests compare with (``generate``,
``paged_beam_search``, a dedicated engine), and, for every kind of row, the
same engine sending one call of ``num_slots`` rows a tick: the shape of before.

All CPU, none timing-sensitive.
"""
import string
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.decoding import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import (_PREFILL_CHUNK_JIT, _PREFILL_JIT,
                                     clear_jit_caches, paged_beam_search)
from paddle_tpu.observability import GOODPUT, TRACER
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.serving.adapters import AdapterStore
from paddle_tpu.serving.executor import _SAMPLE_ROWS_JIT
from paddle_tpu.serving.grammar import TokenMaskAutomaton

CAP = 256
ENG = dict(num_slots=4, block_size=16, max_prompt_len=CAP, max_seq_len=640,
           eos_token_id=None)
# 63 single-char tokens and an empty-string EOS, as tests/test_grammar.py's
VOCAB = list(string.digits + string.ascii_lowercase
             + string.ascii_uppercase[:19] + '{}":,-._') + [""]
EOS = 63


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64, dtype=jnp.float32)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def store(model):
    import jax
    from paddle_tpu.peft import lora_init, lora_state_dict
    s = AdapterStore(model, capacity=2, max_rank=4)
    for name, seed in (("t1", 1), ("t2", 2)):
        sd = lora_state_dict(lora_init(
            model, jax.random.PRNGKey(seed), r=4, alpha=8,
            target_modules=("qkv_proj", "o_proj")))
        rs = np.random.RandomState(seed)
        for k in list(sd):
            if k.endswith(".lora_B"):
                sd[k] = rs.randn(*np.shape(sd[k])).astype(np.float32) * 0.05
        s.register(name, sd)
    return s


def _solo(model, p, n):
    return [int(t) for t in np.asarray(generate(
        model, jnp.asarray(np.asarray(p)[None]), max_new_tokens=n))[0, len(p):]]


def _spans(name):
    return [e for e in TRACER.export()["traceEvents"]
            if e["ph"] == "X" and e["name"].startswith(name)]


def _traced(eng):
    """Run the engine dry under the tracer -> its spans' ``args`` by tick:
    [(serving.prefill's, [exe.prefill*'s, in order]), ...] for the ticks
    that sent a call."""
    TRACER.clear()
    TRACER.enable()
    try:
        eng.run()
    finally:
        TRACER.disable()
    sent = sorted(_spans("exe.prefill"), key=lambda e: e["ts"])
    ticks = [(tick["args"], [e for e in sent if e["parent"] == tick["id"]])
             for tick in _spans("serving.prefill")]
    TRACER.clear()
    return [t for t in ticks if t[1]]


# ------------------------------------------------------------- the rule
@pytest.mark.parametrize("slots, cap, rows", [
    (16, 256, 1), (8, 256, 1),         # the benchmark's two serving cells
    (3, 8, 3), (4, 16, 4),             # short rows: every slot's, as before
    (16, 64, 4), (16, 100, 2), (2, 1024, 1), (1, 8, 1)])
def test_rows_a_call_follow_from_the_shapes(model, slots, cap, rows):
    eng = LLMEngine(model, num_slots=slots, block_size=4, max_prompt_len=cap,
                    max_seq_len=cap + 8, num_blocks=8)
    assert eng.prefill_rows == rows


# ------------------------------------ more live rows than a call holds
KINDS = ["plain", "adapters", "grammar", "int8", "sampled"]


def _requests(kind, prompts, new):
    """One request a prompt, of the kind asked for, and what to build the
    engine with."""
    kw = [{} for _ in prompts]
    if kind == "adapters":                 # two adapters and a base row
        for k, aid in zip(kw, ("t1", "t2", None, "t1")):
            k["adapter_id"] = aid
    if kind == "grammar":                  # the first token is bound
        kw[1]["grammar"] = TokenMaskAutomaton("[ab]{40}", vocab=VOCAB,
                                              eos_token_id=EOS)
    if kind == "sampled":
        for i, k in enumerate(kw):
            k.update(temperature=0.7 + 0.1 * i, top_p=0.9)
    return [Request(p, max_new_tokens=new, **k) for p, k in zip(prompts, kw)]


def _serve(model, store, kind, prompts, new, rows=None, before=None):
    opts = dict(ENG)
    if kind == "adapters":
        opts["adapter_store"] = store
    if kind == "int8":
        opts["kv_dtype"] = "int8"
    eng = LLMEngine(model, **opts)
    assert eng.prefill_rows == 1
    if rows is not None:
        eng.prefill_rows = rows            # the reference: one call a tick
    if before is not None:    # served first, to fill the cache (a trie an
        for aid in ("t1", "t2", None) if kind == "adapters" else (None,):
            eng.add_request(Request(before, max_new_tokens=2,     # adapter)
                                    adapter_id=aid))
        eng.run()
        eng.pop_finished()
    reqs = _requests(kind, prompts, new)
    rids = [eng.add_request(r) for r in reqs]
    ticks = _traced(eng)
    eng.assert_quiescent()
    return [list(eng.requests[r].tokens) for r in rids], ticks, reqs


def _check_kind(model, kind, prompts, got, reqs, new):
    if kind == "plain":
        for p, toks in zip(prompts, got):
            assert toks == _solo(model, p, new)
    if kind == "grammar":
        aut, sid = reqs[1].grammar, reqs[1].grammar.start_state
        for t in got[1]:
            assert aut.mask(sid)[t]
            sid = aut.advance(sid, t)
        assert got[0] == _solo(model, prompts[0], new)   # free rows: unbound
        assert got[1] != _solo(model, prompts[1], new)
    if kind == "adapters":
        assert got[0] != got[2] and got[1] != got[2]


@pytest.mark.parametrize("kind", KINDS)
def test_a_burst_of_admissions_is_sent_a_row_a_call(model, store, kind):
    """Four prompts admitted in one tick, one row a call: four calls of the
    whole-prompt program, then the tokens of one call of four rows."""
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, 63, (n,)) for n in (9, 200, 33, 120)]
    got, ticks, reqs = _serve(model, store, kind, prompts, 5)
    want, ref_ticks, _ = _serve(model, store, kind, prompts, 5, rows=4)
    if kind != "sampled":                  # a key a call draws other tokens
        assert got == want
    assert all(len(t) == 5 for t in got)
    _check_kind(model, kind, prompts, got, reqs, 5)
    (tick, calls), = ticks
    assert tick == {"live_rows": 4, "calls": 4, "greedy": kind != "sampled"}
    assert [c["name"] for c in calls] == ["exe.prefill"] * 4
    assert [c["args"]["rows"] for c in calls] == [CAP] * 4
    assert [c["args"]["useful"] for c in calls] == [9, 200, 33, 120]
    (tick, calls), = ref_ticks
    assert tick == {"live_rows": 4, "calls": 1, "greedy": kind != "sampled"}
    assert calls[0]["args"]["rows"] == 4 * CAP


@pytest.mark.parametrize("kind", KINDS)
def test_rows_chunking_together_are_sent_a_row_a_call(model, store, kind):
    """A long prompt beside three that begin with a cached one: all four
    take the chunk program in one tick, from their own offsets."""
    rs = np.random.RandomState(8)
    shared = rs.randint(1, 63, (40,))
    prompts = [rs.randint(1, 63, (300,))] + [
        np.concatenate([shared, rs.randint(1, 63, (n,))]) for n in (5, 30, 70)]
    got, ticks, reqs = _serve(model, store, kind, prompts, 4, before=shared)
    want, ref_ticks, _ = _serve(model, store, kind, prompts, 4, rows=4,
                                before=shared)
    if kind != "sampled":
        assert got == want
    assert all(len(t) == 4 for t in got)
    _check_kind(model, kind, prompts, got, reqs, 4)
    ticks = [t for t in ticks if t[1][0]["name"] == "exe.prefill_chunk"]
    greedy = kind != "sampled"       # of the calls that chose a first token
    assert ticks[0][0] == {"live_rows": 4, "calls": 4, "greedy": greedy}
    # the cached prompt's 40 tokens are hits, its partial block copied
    assert [c["args"]["useful"] for c in ticks[0][1]] == [256, 5, 30, 70]
    assert ticks[1][0] == {"live_rows": 1, "calls": 1,      # the long one's
                           "greedy": greedy}
    assert ticks[1][1][0]["args"] == {**ticks[1][1][0]["args"],
                                      "rows": CAP, "useful": 44}
    ref = [t for t in ref_ticks if t[1][0]["name"] == "exe.prefill_chunk"]
    assert ref[0][0] == {"live_rows": 4, "calls": 1, "greedy": greedy}


def test_a_beam_rides_the_admission_calls_as_one_more_row(model):
    """Two greedy prompts and a beam request of two in one tick: three rows,
    three calls, the beam's first select from the last call's logits."""
    rs = np.random.RandomState(9)
    p0, p1, pb = (rs.randint(2, 63, (n,)) for n in (11, 150, 37))
    ref_seq, ref_score = paged_beam_search(model, pb, max_new_tokens=5,
                                           num_beams=2, eos_token_id=1,
                                           block_size=16)
    eng = LLMEngine(model, **{**ENG, "eos_token_id": 1})
    r0 = eng.add_request(Request(p0, max_new_tokens=5))
    rb = eng.add_request(Request(pb, max_new_tokens=5, num_beams=2))
    r1 = eng.add_request(Request(p1, max_new_tokens=5))
    ticks = _traced(eng)
    eng.assert_quiescent()
    assert ticks[0][0] == {"live_rows": 3, "calls": 3, "greedy": True}
    assert [c["args"]["useful"] for c in ticks[0][1]] == [11, 150, 37]
    out = {r: list(eng.requests[r].tokens) for r in (r0, r1, rb)}
    assert out[rb] == [int(t) for t in np.asarray(ref_seq)[len(pb):]]
    np.testing.assert_allclose(eng.requests[rb].beam_score, float(ref_score),
                               rtol=1e-5)
    for r, p in ((r0, p0), (r1, p1)):
        solo = np.asarray(generate(model, jnp.asarray(p[None]),
                                   max_new_tokens=5, eos_token_id=1))[0, len(p):]
        assert out[r] == [int(t) for t in solo[:len(out[r])]]


# -------------------------------------------------------- the tight pool
def test_a_later_calls_row_never_evicts_an_earlier_calls(model):
    """Three long prompts chunking on a pool that holds one and a half of
    them: a row whose blocks run dry preempts, and never a row that an
    earlier call of the same tick was sent."""
    rs = np.random.RandomState(10)
    prompts = [rs.randint(1, 63, (700,)) for _ in range(3)]
    eng = LLMEngine(model, num_slots=3, block_size=16, max_prompt_len=CAP,
                    max_seq_len=1024, num_blocks=64, preemption=True,
                    prefix_caching=False, eos_token_id=None)
    rids = [eng.add_request(Request(p, max_new_tokens=4)) for p in prompts]
    sent, evicted = [], []
    send, preempt = eng.exe.prefill_chunk, eng.sched.preempt

    def sending(ids, lens, offs, slots, rows, **kw):
        by_slot = {s: rid for rid, (s, _) in eng.prefilling.items()}
        for slot, row in zip(slots, rows):
            if slot < eng.num_slots:
                rid = by_slot[int(slot)]
                held = eng.mgr.tables[rid]
                assert list(row[:len(held)]) == list(held)
                sent.append(rid)
        return send(ids, lens, offs, slots, rows, **kw)

    def preempting(e, protect_rid=None):
        before = set(eng.prefilling) | {
            int(r) for r in eng.slot_req[eng.active]}
        ok = preempt(e, protect_rid)
        evicted.extend(before - (set(eng.prefilling) | {
            int(r) for r in eng.slot_req[eng.active]}))
        return ok

    eng.exe.prefill_chunk, eng.sched.preempt = sending, preempting
    both = 0
    for _ in range(200):
        if not eng.has_work():
            break
        del sent[:], evicted[:]
        eng.step()
        assert not set(sent) & set(evicted)
        for rid in sent:                   # its scatter landed: still held
            assert rid in eng.prefilling or rid in eng.slot_req \
                or eng.requests[rid].done
        both += len(sent) > 1 and bool(evicted)
    assert not eng.has_work() and both >= 1
    assert eng.stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert list(eng.requests[rid].tokens) == _solo(model, p, 4)


# ------------------------------------------- no new shape in the window
def test_the_window_meets_no_shape_the_warm_up_did_not(model):
    """The benchmark's warm-up, as its serving drivers run it, then what a
    window can bring: a burst that fills every slot, a lone admission, a
    prompt behind a cached one, a prompt over ``max_prompt_len``. One
    compiled entry of each prefill program and of the sampler, before and
    after."""
    root = str(Path(pt.__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.drivers.serve import _warm_up
    jits = (_PREFILL_JIT, _PREFILL_CHUNK_JIT, _SAMPLE_ROWS_JIT)
    clear_jit_caches()
    _SAMPLE_ROWS_JIT.clear_cache()
    rs = np.random.RandomState(11)
    system = [rs.randint(1, 63, (CAP,)) for _ in range(2)]
    eng = LLMEngine(model, **ENG)
    _warm_up(eng, 2 ** 31 + 5, 64, CAP, 16, system)
    assert [j._cache_size() for j in jits] == [1, 1, 1]
    waves = [[rs.randint(1, 63, (n,)) for n in (17, 256, 90, 140)],
             [rs.randint(1, 63, (60,))],
             [np.concatenate([system[1], rs.randint(1, 63, (20,))])],
             [rs.randint(1, 63, (CAP + 1,)), rs.randint(1, 63, (600,)),
              np.concatenate([system[0], rs.randint(1, 63, (300,))])]]
    for wave in waves:
        rids = [eng.add_request(Request(p, max_new_tokens=3)) for p in wave]
        eng.run()
        assert all(len(eng.requests[r].tokens) == 3 for r in rids)
    assert eng.mgr.cache_stats["token_hits"] >= 2 * CAP
    assert [j._cache_size() for j in jits] == [1, 1, 1]


# --------------------------------------------------------- the counters
def test_pad_rows_counts_the_dead_rows_of_the_calls_sent(model):
    """``(prefill_rows x calls - live) x max_prompt_len``: nothing where a
    call holds one row; at two rows a call, three live rows waste one."""
    rs = np.random.RandomState(12)
    prompts = [rs.randint(1, 63, (n,)) for n in (20, 30, 40)]
    for rows, wasted in ((1, 0), (2, CAP)):
        eng = LLMEngine(model, **ENG)
        eng.prefill_rows = rows
        for p in prompts:
            eng.add_request(Request(p, max_new_tokens=1))
        base = GOODPUT.waste_by_why().get("pad_rows", 0)
        (tick, calls), = _traced(eng)
        assert tick == {"live_rows": 3, "calls": -(-3 // rows),
                        "greedy": True}
        assert all(c["args"]["rows"] == rows * CAP for c in calls)
        assert sum(c["args"]["useful"] for c in calls) == 90
        assert GOODPUT.waste_by_why().get("pad_rows", 0) - base == wasted
