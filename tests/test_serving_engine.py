"""Continuous-batching serving engine + paged beam (VERDICT r2 items 2/6).

* 3x more requests than slots all complete; every output equals its
  single-request greedy reference
* queued requests are admitted MID-FLIGHT into freed slots (prefill
  interleaved with decode ticks)
* pool block usage tracks Σ live lengths (lazy allocation), never the
  dense bound
* per-request streaming callbacks fire in decode order
* beam search in the paged path == the static-cache beam, with prompt
  blocks SHARED across beams (refcount fork, partial-tail copy)
Ref: PaddleNLP llm/predict/predictor.py block-attention serving.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.decoding import beam_search, generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import RefBlockManager, paged_beam_search
from paddle_tpu.serving import LLMEngine, Request


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def _prompts(n, rs):
    return [rs.randint(0, 64, (int(l),))
            for l in rs.randint(3, 14, size=n)]


def test_engine_oversubscribed_matches_solo_greedy(model):
    """6 requests through 2 slots: all complete, each == solo greedy."""
    rs = np.random.RandomState(0)
    prompts = _prompts(6, rs)
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=24)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=6))
    out = eng.run()
    assert len(out) == 6
    for rid, toks in out.items():
        p = prompts[rid]
        ref = np.asarray(generate(model, jnp.asarray(p[None]),
                                  max_new_tokens=6))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(toks), ref,
                                      err_msg=f"request {rid}")


def test_engine_admits_mid_flight(model):
    """A queued request must enter a slot while others are mid-decode —
    not after the whole first wave drains."""
    rs = np.random.RandomState(1)
    prompts = _prompts(4, rs)
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=32, eos_token_id=None)
    # first two run long, second two are queued behind them
    for i, p in enumerate(prompts):
        eng.add_request(Request(p, max_new_tokens=10 if i < 2 else 4))
    first_tick_of = {}
    tick = 0
    while eng.has_work():
        for rid, _ in eng.step():
            first_tick_of.setdefault(rid, tick)
        tick += 1
    # requests 2/3 started strictly after 0/1 but before the run ended
    assert first_tick_of[2] > first_tick_of[0]
    assert first_tick_of[2] < tick - 1
    # outputs still exact
    for rid in range(4):
        p = prompts[rid]
        n = 10 if rid < 2 else 4
        ref = np.asarray(generate(model, jnp.asarray(p[None]),
                                  max_new_tokens=n))[0, len(p):]
        np.testing.assert_array_equal(
            np.asarray(eng.requests[rid].tokens), ref)


def test_engine_eos_frees_slot_for_queue(model):
    """EOS finishes a request early; its slot and blocks serve the queue."""
    rs = np.random.RandomState(2)
    prompts = _prompts(4, rs)
    refs = {}
    eos = None
    for rid, p in enumerate(prompts):
        r = np.asarray(generate(model, jnp.asarray(p[None]),
                                max_new_tokens=8))[0, len(p):]
        refs[rid] = r
    # choose the first generated token of request 0 as EOS
    eos = int(refs[0][0])
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=24, eos_token_id=eos)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=8))
    out = eng.run()
    for rid in range(4):
        got = np.asarray(out[rid])
        ref = refs[rid]
        stop = np.nonzero(ref == eos)[0]
        expect = ref[: int(stop[0]) + 1] if len(stop) else ref
        np.testing.assert_array_equal(got, expect, err_msg=f"req {rid}")
        fin = eng.requests[rid].finish_reason
        assert fin == ("eos" if len(stop) else "length")


def test_engine_pool_usage_tracks_live_lengths(model):
    """Lazy allocation: blocks in use ≈ Σ ceil(live_len/bs), and the peak
    stays far under slots × max_blocks when requests are short."""
    rs = np.random.RandomState(3)
    prompts = _prompts(6, rs)
    eng = LLMEngine(model, num_slots=3, block_size=4, max_prompt_len=16,
                    max_seq_len=64)   # roomy tables; usage must stay lazy
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=5))
    peak = 0
    while eng.has_work():
        eng.step()
        used = eng.mgr.num_blocks - eng.mgr.free_blocks
        live = [int(eng.cur[s]) + 1 for s in range(eng.num_slots)
                if eng.slot_req[s] >= 0]
        bound = sum(-(-n // eng.block_size) for n in live)
        assert used <= bound + eng.num_slots  # ≤ one growth block per slot
        peak = max(peak, used)
    assert peak <= 3 * (-(-(16 + 5) // 4))   # ≈ Σ active, not table width
    assert eng.mgr.free_blocks == eng.mgr.num_blocks  # all recycled


def test_engine_streaming_callbacks(model):
    rs = np.random.RandomState(4)
    p = rs.randint(0, 64, (5,))
    seen = []
    eng = LLMEngine(model, num_slots=1, block_size=4, max_prompt_len=8,
                    max_seq_len=16)
    eng.add_request(Request(p, max_new_tokens=5,
                            stream=lambda r, t: seen.append(t)))
    out = eng.run()
    assert seen == out[0] and len(seen) == 5


def test_engine_sampling_seeded(model):
    """temperature > 0: engine runs, tokens in-vocab, reproducible."""
    rs = np.random.RandomState(5)
    prompts = _prompts(3, rs)

    def run():
        eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                        max_seq_len=24, temperature=0.8, top_k=8, seed=7)
        for p in prompts:
            eng.add_request(Request(p, max_new_tokens=6))
        return eng.run()

    a, b = run(), run()
    assert all(len(v) == 6 for v in a.values())
    assert all(0 <= t < 64 for v in a.values() for t in v)
    assert a == b


def test_engine_sliding_window_recycles_blocks(model):
    """Mistral-style window: outputs equal the static ring-cache generate
    AND live blocks per sequence stay O(window), not O(length)."""
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64, sliding_window=6)
    wmodel = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, 64, (n,)) for n in (10, 4)]
    new = 16   # decode far past the window

    eng = LLMEngine(wmodel, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=32)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=new))
    peak_live = 0
    while eng.has_work():
        eng.step()
        for s in range(eng.num_slots):
            if eng.slot_req[s] >= 0:
                peak_live = max(peak_live,
                                eng._live_blocks(int(eng.slot_req[s])))
    for rid, p in enumerate(prompts):
        ref = np.asarray(generate(wmodel, jnp.asarray(p[None]),
                                  max_new_tokens=new))[0, len(p):]
        np.testing.assert_array_equal(
            np.asarray(eng.requests[rid].tokens), ref, err_msg=f"req {rid}")
    # window 6 @ bs 4: live span ≤ window + 2*bs tokens -> 4 blocks; the
    # un-recycled bound for row 0 would be ceil((10+16)/4) = 7
    assert peak_live <= 4, peak_live


def test_engine_request_validation_and_eviction(model):
    eng = LLMEngine(model, num_slots=1, block_size=4, max_prompt_len=8,
                    max_seq_len=16)
    with pytest.raises(ValueError, match="at least one token"):
        eng.generate([])
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.generate([1, 2], max_new_tokens=0)
    rid = eng.add_request(Request([1, 2, 3], max_new_tokens=2, req_id=5))
    assert rid == 5
    with pytest.raises(ValueError, match="already exists"):
        eng.add_request(Request([4], max_new_tokens=2, req_id=5))
    auto = eng.generate([7, 8], max_new_tokens=2)
    assert auto > 5                      # auto ids skip explicit ones
    eng.run()
    done = eng.pop_finished()
    assert set(done) == {5, auto} and all(r.done for r in done.values())
    assert eng.requests == {}            # evicted — no unbounded growth


# ------------------------------------------------------------------- beam

def test_paged_beam_matches_static_beam(model):
    rs = np.random.RandomState(6)
    prompt = rs.randint(0, 64, (7,))
    ref_seq, ref_score = beam_search(model, jnp.asarray(prompt[None]),
                                     max_new_tokens=8, num_beams=4)
    got_seq, got_score = paged_beam_search(model, prompt, max_new_tokens=8,
                                           num_beams=4, block_size=4)
    np.testing.assert_array_equal(np.asarray(got_seq),
                                  np.asarray(ref_seq)[0])
    assert abs(float(got_score) - float(ref_score[0])) < 1e-5


def test_paged_beam_with_eos_matches_static(model):
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, 64, (6,))
    probe, _ = beam_search(model, jnp.asarray(prompt[None]),
                           max_new_tokens=8, num_beams=4)
    eos = int(np.asarray(probe)[0, len(prompt) + 2])
    ref_seq, ref_score = beam_search(model, jnp.asarray(prompt[None]),
                                     max_new_tokens=8, num_beams=4,
                                     eos_token_id=eos)
    got_seq, got_score = paged_beam_search(model, prompt, max_new_tokens=8,
                                           num_beams=4, block_size=4,
                                           eos_token_id=eos)
    np.testing.assert_array_equal(np.asarray(got_seq),
                                  np.asarray(ref_seq)[0])
    assert abs(float(got_score) - float(ref_score[0])) < 1e-5


def test_paged_beam_shares_prompt_blocks(model):
    """K beams over a long prompt must NOT use K x prompt blocks: full
    prompt blocks are refcount-shared, only tails are private."""
    rs = np.random.RandomState(8)
    prompt = rs.randint(0, 64, (12,))   # 3 full blocks at bs=4
    K, bs = 4, 4
    pool = K * (-(-(len(prompt) + 4) // bs))
    seq, _ = paged_beam_search(model, prompt, max_new_tokens=4,
                               num_beams=K, block_size=bs, num_blocks=pool)
    assert len(np.asarray(seq)) == len(prompt) + 4
    # direct manager-level check of the sharing arithmetic
    mgr = RefBlockManager(num_blocks=pool, block_size=bs)
    mgr.allocate(0, len(prompt))
    base = mgr.num_blocks - mgr.free_blocks
    for j in range(1, K):
        assert mgr.fork(0, j, len(prompt)) is None   # aligned: no copy
    assert mgr.num_blocks - mgr.free_blocks == base  # fully shared
    mgr2 = RefBlockManager(num_blocks=pool, block_size=bs)
    mgr2.allocate(0, 10)                              # partial tail
    used0 = mgr2.num_blocks - mgr2.free_blocks
    assert mgr2.fork(0, 1, 10) is not None            # tail copied
    assert mgr2.num_blocks - mgr2.free_blocks == used0 + 1
    mgr2.free(1)
    assert mgr2.num_blocks - mgr2.free_blocks == used0


def test_the_programs_are_handed_the_model_flattened_once(model):
    """The executor flattens the model when it is built: a jitted program
    then walks a flat list and not a tree of modules on every tick, sees
    the Module it always saw when it is traced, and engines over models
    of one structure still share one trace."""
    pt.seed(1)
    other = LlamaForCausalLM(model.cfg)
    a = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                  max_seq_len=24)
    b = LLMEngine(other, num_slots=2, block_size=4, max_prompt_len=16,
                  max_seq_len=24)
    flat = a.exe._model
    assert flat.key is b.exe._model.key
    assert len(flat.leaves) == len(jax.tree_util.tree_leaves(model))

    seen = []

    @jax.jit
    def head_sum(m):
        seen.append(type(m))
        return m.lm_head.sum()

    np.testing.assert_allclose(head_sum(flat), model.lm_head.sum(), rtol=1e-6)
    head_sum(b.exe._model)                      # same structure: no retrace
    assert seen == [LlamaForCausalLM]
