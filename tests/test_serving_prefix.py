"""Serving: cross-request prefix caching + preemption (VERDICT r3 item 4).

* two requests sharing a prompt prefix allocate the prefix blocks ONCE
  (pool accounting assertion), both concurrent and sequential
* parked (finished-request) blocks are reclaimed by LRU eviction when the
  free list runs dry — caching never reduces usable capacity
* preemption mode admits more concurrent work than worst-case reservation
  allows, preempts the youngest slot on out-of-blocks, and the victim
  resumes with recompute — all outputs stay exactly solo-greedy
Ref capability: PaddleNLP llm/predict block-attention serving (vLLM-style
block reuse + recompute preemption).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.decoding import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import RadixPrefixBlockManager
from paddle_tpu.serving import LLMEngine, Request


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def _solo(model, p, n):
    return np.asarray(generate(model, jnp.asarray(np.asarray(p)[None]),
                               max_new_tokens=n))[0, len(p):]


# --------------------------------------------------------------- manager
def test_manager_park_match_adopt_evict():
    mgr = RadixPrefixBlockManager(num_blocks=6, block_size=4)
    toks = np.arange(10, dtype=np.int32)          # 2 full blocks + tail
    mgr.allocate(1, 10)
    mgr.commit_prefix(1, toks)
    t1 = list(mgr.tables[1])
    # capped at len-1 so the last token always prefills: the whole blocks
    # are shared, one token of the tail block is offered copy-on-write
    m = mgr.match_prefix(toks)
    assert (m.blocks, m.token_count, m.cow) == (t1[:2], 9, (t1[2], 1))
    m = mgr.match_prefix(np.arange(9, dtype=np.int32))
    assert (m.blocks, m.token_count, m.cow) == (t1[:2], 8, None)
    # a diverging second block only matches the first
    other = np.concatenate([np.arange(4), np.full(6, 63)]).astype(np.int32)
    m = mgr.match_prefix(other)
    assert (m.blocks, m.token_count, m.cow) == (t1[:1], 4, None)
    # free -> every block the trie holds parks (still matchable)
    mgr.free(1)
    assert mgr.match_prefix(toks).blocks == t1[:2]
    assert mgr._parked == set(t1)
    assert mgr.free_blocks == 6                    # parked counts as free
    # adopt revives the parked blocks
    adopted = mgr.match_prefix(np.arange(9, dtype=np.int32))
    mgr.adopt_prefix(2, adopted)
    assert not set(adopted.blocks) & mgr._parked
    mgr.free(2)
    # exhaust the free list: eviction reclaims parked blocks LRU-first
    mgr.allocate(3, 24)                            # all 6 blocks
    assert mgr.cache_stats["evictions"] == 3
    assert not mgr.match_prefix(toks)              # the trie is empty


# ------------------------------------------------------- prefix caching
def test_concurrent_prefix_shared_once(model):
    rs = np.random.RandomState(3)
    pre = rs.randint(0, 64, (8,))
    p1 = np.concatenate([pre, rs.randint(0, 64, (4,))])
    p2 = np.concatenate([pre, rs.randint(0, 64, (4,))])
    eng = LLMEngine(model, num_slots=4, block_size=4, max_prompt_len=16,
                    max_seq_len=24)
    r1 = eng.add_request(Request(p1, max_new_tokens=5))
    r2 = eng.add_request(Request(p2, max_new_tokens=5))
    eng.step()                                     # both admitted this tick
    # pool accounting: the 2 full prefix blocks exist ONCE across tables
    t1, t2 = eng.mgr.tables[r1], eng.mgr.tables[r2]
    assert t1[:2] == t2[:2], "prefix blocks not shared"
    assert eng.mgr._rc[t1[0]] == 2 and eng.mgr._rc[t1[1]] == 2
    assert eng.mgr.cache_stats["hit_blocks"] == 2
    distinct = set(t1) | set(t2)
    assert len(distinct) == len(t1) + len(t2) - 2
    out = eng.run()
    np.testing.assert_array_equal(out[r1], _solo(model, p1, 5))
    np.testing.assert_array_equal(out[r2], _solo(model, p2, 5))


def test_sequential_prefix_reuse_after_finish(model):
    rs = np.random.RandomState(4)
    pre = rs.randint(0, 64, (9,))
    p1 = np.concatenate([pre, rs.randint(0, 64, (3,))])
    p2 = np.concatenate([pre, rs.randint(0, 64, (2,))])
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=24)
    r1 = eng.add_request(Request(p1, max_new_tokens=4))
    out1 = eng.run()
    np.testing.assert_array_equal(out1[r1], _solo(model, p1, 4))
    # r1 finished; its hashed prompt blocks are parked, then re-shared
    r2 = eng.add_request(Request(p2, max_new_tokens=4))
    out2 = eng.run()
    assert eng.mgr.cache_stats["hit_blocks"] == 2   # pre covers 2 blocks
    np.testing.assert_array_equal(out2[r2], _solo(model, p2, 4))


def test_long_prompt_chunked_prefix_reuse(model):
    """Chunked prefill (prompt > max_prompt_len) commits its prefix;
    an identical later prompt skips the cached chunks entirely."""
    rs = np.random.RandomState(5)
    p = rs.randint(0, 64, (20,))                   # > max_prompt_len=8
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=8,
                    max_seq_len=32)
    r1 = eng.add_request(Request(p, max_new_tokens=4))
    out1 = eng.run()
    sol = _solo(model, p, 4)
    np.testing.assert_array_equal(out1[r1], sol)
    r2 = eng.add_request(Request(p.copy(), max_new_tokens=4))
    ticks = 0
    while eng.has_work():
        eng.step()
        ticks += 1
    # 4 of the 5 prompt blocks were cached ((20-1)//4 = 4): one chunk tick
    # covers the 4-token suffix, so first token lands on tick 1
    assert eng.mgr.cache_stats["hit_blocks"] >= 4
    np.testing.assert_array_equal(eng.requests[r2].tokens, sol)


def test_eviction_under_pressure_stays_correct(model):
    """Fill the pool with parked blocks, then admit work that needs them:
    eviction must reclaim transparently."""
    rs = np.random.RandomState(6)
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=20, num_blocks=10)
    outs = {}
    prompts = {}
    for i in range(4):      # sequential: 3 hashed blocks park per request
        p = rs.randint(0, 64, (15,))
        rid = eng.add_request(Request(p, max_new_tokens=4))
        prompts[rid] = p
        outs.update(eng.run())
    assert eng.mgr.cache_stats["evictions"] > 0
    for rid, toks in outs.items():
        np.testing.assert_array_equal(toks, _solo(model, prompts[rid], 4))


# ----------------------------------------------------------- preemption
def test_preemption_oversubscribes_and_matches_solo(model):
    """Pool too small for both worst cases: worst-case admission would
    serialise; preemption runs them concurrently, evicts the youngest
    when blocks run out, and still reproduces solo greedy exactly."""
    rs = np.random.RandomState(7)
    p1 = rs.randint(0, 64, (7,))
    p2 = rs.randint(0, 64, (7,))
    n_new = 12
    # worst case each: ceil((7+12)/4) = 5 blocks; pool of 7 can't reserve 10
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=19, num_blocks=7, preemption=True)
    r1 = eng.add_request(Request(p1, max_new_tokens=n_new))
    r2 = eng.add_request(Request(p2, max_new_tokens=n_new))
    both_active = False
    while eng.has_work():
        eng.step()
        both_active |= bool(eng.active.sum() == 2)
    assert both_active, "preemption should admit both concurrently"
    assert eng.stats["preemptions"] >= 1
    np.testing.assert_array_equal(eng.requests[r1].tokens,
                                  _solo(model, p1, n_new))
    np.testing.assert_array_equal(eng.requests[r2].tokens,
                                  _solo(model, p2, n_new))
    # the victim's resume re-shared its own parked prompt block
    assert eng.mgr.cache_stats["hit_blocks"] >= 1


def test_worst_case_mode_never_runs_both(model):
    """Control for the test above: same sizes WITHOUT preemption keep the
    second request queued until the first finishes (and never preempt)."""
    rs = np.random.RandomState(7)
    p1 = rs.randint(0, 64, (7,))
    p2 = rs.randint(0, 64, (7,))
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=19, num_blocks=7)
    r1 = eng.add_request(Request(p1, max_new_tokens=12))
    r2 = eng.add_request(Request(p2, max_new_tokens=12))
    both = False
    while eng.has_work():
        eng.step()
        both |= bool(eng.active.sum() == 2)
    assert not both
    assert eng.stats["preemptions"] == 0
    np.testing.assert_array_equal(eng.requests[r1].tokens,
                                  _solo(model, p1, 12))
    np.testing.assert_array_equal(eng.requests[r2].tokens,
                                  _solo(model, p2, 12))


def test_preemption_many_requests_fcfs_progress(model):
    """6 long-running requests through 3 slots on a tight pool: everyone
    completes, all exactly solo-greedy, under repeated preemption."""
    rs = np.random.RandomState(8)
    prompts = [rs.randint(0, 64, (int(l),))
               for l in rs.randint(5, 12, size=6)]
    eng = LLMEngine(model, num_slots=3, block_size=4, max_prompt_len=16,
                    max_seq_len=24, num_blocks=12, preemption=True)
    rids = [eng.add_request(Request(p, max_new_tokens=8)) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(out[rid], _solo(model, p, 8),
                                      err_msg=f"request {rid}")


def test_prefix_caching_disabled_flag(model):
    """prefix_caching=False must behave exactly as before (no sharing)."""
    rs = np.random.RandomState(9)
    pre = rs.randint(0, 64, (8,))
    p1 = np.concatenate([pre, rs.randint(0, 64, (3,))])
    p2 = np.concatenate([pre, rs.randint(0, 64, (3,))])
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=24, prefix_caching=False)
    r1 = eng.add_request(Request(p1, max_new_tokens=4))
    r2 = eng.add_request(Request(p2, max_new_tokens=4))
    out = eng.run()
    assert eng.mgr.cache_stats["hit_blocks"] == 0
    np.testing.assert_array_equal(out[r1], _solo(model, p1, 4))
    np.testing.assert_array_equal(out[r2], _solo(model, p2, 4))
