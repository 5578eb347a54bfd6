"""Radix-trie prefix cache: token-level sharing + copy-on-write (ISSUE 10).

* trie insert/match/split on block-edge boundaries; partial tails match
  at TOKEN granularity (flat hash-block caching would score zero here)
* COW fork mid-block: the boundary block is shared read-only, the
  adopter gets a private copy via the host-side copy plan; a cancelled
  adopter (freed before the plan drains) leaks nothing
* leaf-LRU eviction reclaims parked blocks least-recently-touched
  first; the ``serving.prefix_evict`` chaos site is exception-atomic
* refcount conservation under adopt/free interleavings
* engine-level: greedy outputs identical cache-on vs cache-off vs fresh
  engine, including preempt+replay and chunked prefill
Ref capability: SGLang RadixAttention over vLLM-style paging.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.decoding import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import PrefixMatch, RadixPrefixBlockManager
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.utils.faults import FAULTS, InjectedFault


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


# ----------------------------------------------------------- trie unit
def test_match_token_granularity_and_cow_offer():
    mgr = RadixPrefixBlockManager(num_blocks=8, block_size=4)
    toks = np.arange(10, dtype=np.int32)           # 2 full blocks + tail(2)
    mgr.allocate(1, 10)
    mgr.commit_prefix(1, toks)
    t1 = list(mgr.tables[1])
    # identical prompt: cap at len-1 -> 9 tokens = 2 full blocks + 1 COW tok
    m = mgr.match_prefix(toks)
    assert isinstance(m, PrefixMatch)
    assert list(m) == t1[:2] and len(m) == 2
    assert m.token_count == 9
    assert m.cow == (t1[2], 1)
    # divergence mid-block 2: 6 shared tokens -> 1 full block + 2 COW toks
    other = np.concatenate([toks[:6], np.full(6, 63)]).astype(np.int32)
    m2 = mgr.match_prefix(other)
    assert list(m2) == t1[:1]
    assert m2.token_count == 6 and m2.cow == (t1[1], 2)
    # exact block-boundary divergence: full blocks only, no COW
    edge = np.concatenate([toks[:8], np.full(4, 63)]).astype(np.int32)
    m3 = mgr.match_prefix(edge)
    assert list(m3) == t1[:2] and m3.cow is None and m3.token_count == 8
    # no overlap at all is falsy
    assert not mgr.match_prefix(np.full(8, 50, np.int32))
    assert mgr.cache_stats["lookup_tokens"] > 0


def test_commit_extends_partial_tail_in_place():
    mgr = RadixPrefixBlockManager(num_blocks=8, block_size=4)
    toks = np.arange(14, dtype=np.int32)
    mgr.allocate(1, 10)
    mgr.commit_prefix(1, toks[:10])                # partial tail (2 tokens)
    mgr.allocate(1, 14)                            # same seq grows
    mgr.commit_prefix(1, toks)                     # extends, no new node
    t1 = list(mgr.tables[1])
    assert len(mgr._root.children) == 1            # one edge, extended
    m = mgr.match_prefix(np.append(toks, 63).astype(np.int32))
    assert list(m) == t1[:3]
    assert m.token_count == 14 and m.cow == (t1[3], 2)


def test_split_on_block_boundary_shares_both_branches():
    mgr = RadixPrefixBlockManager(num_blocks=12, block_size=4)
    a = np.arange(12, dtype=np.int32)
    b = np.concatenate([a[:8], np.full(4, 60)]).astype(np.int32)
    mgr.allocate(1, 12)
    mgr.commit_prefix(1, a)
    ta = list(mgr.tables[1])
    mgr.allocate(2, 12)
    mgr.commit_prefix(2, b)                        # splits a's edge at 8
    tb = list(mgr.tables[2])
    upper = mgr._root.children[0]
    assert len(upper.tokens) == 8 and len(upper.children) == 2
    # querying either branch walks the shared upper then its own tail
    ma = mgr.match_prefix(np.append(a, 63).astype(np.int32))
    assert list(ma) == ta[:3] and ma.token_count == 12
    mb = mgr.match_prefix(np.append(b, 63).astype(np.int32))
    assert list(mb) == ta[:2] + [tb[2]] and mb.token_count == 12


def test_cow_adopt_copy_plan_and_refcounts():
    mgr = RadixPrefixBlockManager(num_blocks=8, block_size=4)
    toks = np.arange(10, dtype=np.int32)
    mgr.allocate(1, 10)
    mgr.commit_prefix(1, toks)
    t1 = list(mgr.tables[1])
    m = mgr.match_prefix(toks)                     # 2 shared + COW on t1[2]
    table = mgr.adopt_prefix(2, m)
    assert table[:2] == t1[:2]
    dst = table[2]
    assert dst not in t1                           # private copy block
    assert mgr._rc[t1[0]] == 2 and mgr._rc[t1[1]] == 2
    assert mgr._rc[t1[2]] == 2                     # src pinned until drain
    assert mgr._rc[dst] == 1
    assert mgr.cache_stats["partial_hits"] == 1
    assert mgr.cache_stats["token_hits"] == 9
    plan = mgr.take_copy_plan()
    assert plan == [(t1[2], dst)]
    assert mgr._rc[t1[2]] == 1                     # pin dropped
    assert mgr.take_copy_plan() == []              # drained once
    mgr.free(2)
    mgr.free(1)
    assert mgr.free_blocks == mgr.num_blocks       # parked counts as free
    assert not mgr._rc


def test_cow_cancelled_before_drain_leaks_nothing():
    mgr = RadixPrefixBlockManager(num_blocks=6, block_size=4)
    toks = np.arange(7, dtype=np.int32)
    mgr.allocate(1, 7)
    mgr.commit_prefix(1, toks)
    mgr.free(1)                                    # both blocks park
    m = mgr.match_prefix(toks)                     # 1 shared + COW (2 toks)
    assert m.cow is not None
    mgr.adopt_prefix(2, m)
    mgr.free(2)                                    # adopter dies pre-drain
    assert mgr.take_copy_plan() == []              # order cancelled
    assert mgr.free_blocks == mgr.num_blocks
    assert not mgr._rc and not mgr._copy_dst


def test_leaf_lru_eviction_order():
    mgr = RadixPrefixBlockManager(num_blocks=4, block_size=4)
    a = np.arange(4, dtype=np.int32)
    b = np.arange(10, 14, dtype=np.int32)
    mgr.allocate(1, 4)
    mgr.commit_prefix(1, a)
    mgr.free(1)
    mgr.allocate(2, 4)
    mgr.commit_prefix(2, b)
    mgr.free(2)                                    # both parked
    assert mgr.free_blocks == 4
    # touch a AFTER b was committed: b is now the LRU leaf
    assert mgr.match_prefix(np.append(a, 63).astype(np.int32)).token_count \
        == 4
    mgr.allocate(3, 12)                            # 2 free + 1 eviction
    assert mgr.cache_stats["evictions"] == 1
    assert not mgr.match_prefix(np.append(b, 63).astype(np.int32))  # b gone
    assert mgr.match_prefix(np.append(a, 63).astype(np.int32)).token_count \
        == 4                                       # a survived
    mgr.allocate(4, 4)                             # forces a's eviction too
    assert mgr.cache_stats["evictions"] == 2
    assert not mgr.match_prefix(np.append(a, 63).astype(np.int32))
    mgr.free(3)
    mgr.free(4)
    assert mgr.free_blocks == mgr.num_blocks


def test_eviction_truncates_tail_blockwise():
    """Eviction reclaims ONE tail block at a time: a 3-block edge loses
    its deepest block first and the shorter prefix stays matchable."""
    mgr = RadixPrefixBlockManager(num_blocks=3, block_size=4)
    toks = np.arange(12, dtype=np.int32)
    mgr.allocate(1, 12)
    mgr.commit_prefix(1, toks)
    mgr.free(1)
    mgr.allocate(2, 4)                             # evicts deepest block
    assert mgr.cache_stats["evictions"] == 1
    m = mgr.match_prefix(np.append(toks, 63).astype(np.int32))
    assert m.token_count == 8                      # first 2 blocks remain
    mgr.free(2)


def test_chaos_prefix_evict_exception_atomic():
    mgr = RadixPrefixBlockManager(num_blocks=2, block_size=4)
    toks = np.arange(8, dtype=np.int32)
    mgr.allocate(1, 8)
    mgr.commit_prefix(1, toks)
    mgr.free(1)                                    # pool fully parked
    epoch = mgr.cache_epoch
    with FAULTS.scope("serving.prefix_evict", exc=InjectedFault,
                      every=1, times=1):
        with pytest.raises(InjectedFault):
            mgr.allocate(2, 4)
    mgr.tables.pop(2, None)                        # caller cleanup on fail
    # pre-mutation site: trie, parked set, stats, epoch all untouched
    assert mgr.cache_stats["evictions"] == 0
    assert mgr.cache_epoch == epoch
    assert mgr.free_blocks == mgr.num_blocks
    assert mgr.match_prefix(np.append(toks, 63).astype(np.int32)) \
        .token_count == 8
    # and the retried allocation succeeds once the fault clears
    mgr.allocate(2, 4)
    assert mgr.cache_stats["evictions"] == 1
    mgr.free(2)


def test_match_memo_invalidated_by_epoch():
    """cache_epoch bumps on commit AND eviction — the scheduler's memo
    key."""
    mgr = RadixPrefixBlockManager(num_blocks=2, block_size=4)
    e0 = mgr.cache_epoch
    mgr.allocate(1, 8)
    mgr.commit_prefix(1, np.arange(8, dtype=np.int32))
    assert mgr.cache_epoch > e0
    e1 = mgr.cache_epoch
    mgr.free(1)
    mgr.allocate(2, 8)                         # forces eviction
    assert mgr.cache_epoch > e1
    mgr.free(2)


# --------------------------------------------------------- engine level
def test_engine_partial_tail_cow_reuse(model):
    """7-token shared prefix over block_size=4: flat caching scores one
    block; the trie shares 7 of 7 tokens (1 block + 3 COW) and the
    output stays exactly solo-greedy."""
    rs = np.random.RandomState(11)
    pre = rs.randint(0, 64, (7,))
    p1 = np.concatenate([pre, rs.randint(0, 64, (4,))])
    p2 = np.concatenate([pre, rs.randint(0, 64, (4,))])
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=24)
    r1 = eng.add_request(Request(p1, max_new_tokens=4))
    out1 = eng.run()
    r2 = eng.add_request(Request(p2, max_new_tokens=4))
    out2 = eng.run()
    assert eng.mgr.cache_stats["partial_hits"] >= 1
    assert eng.mgr.cache_stats["token_hits"] >= 7
    np.testing.assert_array_equal(out1[r1], _solo(model, p1, 4))
    np.testing.assert_array_equal(out2[r2], _solo(model, p2, 4))
    eng.assert_quiescent()


def test_engine_greedy_identity_on_vs_off(model):
    """The same prompt stream produces bit-identical greedy tokens on a
    warm radix engine, a cache-disabled engine (``prefix_caching=False``),
    and a fresh solo generate — and only the first reuses anything."""
    rs = np.random.RandomState(12)
    pre = rs.randint(0, 64, (9,))
    prompts = [np.concatenate([pre, rs.randint(0, 64, (3,))])
               for _ in range(3)]

    def run_stream(**kw):
        eng = LLMEngine(model, num_slots=2, block_size=4,
                        max_prompt_len=16, max_seq_len=24, **kw)
        outs = []
        for p in prompts:                          # sequential: warm cache
            rid = eng.add_request(Request(p, max_new_tokens=5))
            outs.append(eng.run()[rid])
        return outs, eng.mgr.cache_stats["token_hits"]

    radix, radix_hits = run_stream()
    off, off_hits = run_stream(prefix_caching=False)
    assert radix_hits >= 2 * 9 and off_hits == 0
    for p, a, b in zip(prompts, radix, off):
        sol = _solo(model, p, 5)
        np.testing.assert_array_equal(a, sol)
        np.testing.assert_array_equal(b, sol)


def test_engine_preempt_replay_radix_identity(model):
    """Oversubscribed pool with preemption: the victim's replay re-shares
    its own committed span through the trie and every output matches
    solo greedy."""
    rs = np.random.RandomState(13)
    p1 = rs.randint(0, 64, (7,))
    p2 = rs.randint(0, 64, (7,))
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=19, num_blocks=7, preemption=True)
    r1 = eng.add_request(Request(p1, max_new_tokens=12))
    r2 = eng.add_request(Request(p2, max_new_tokens=12))
    out = eng.run()
    assert eng.stats["preemptions"] >= 1
    assert eng.mgr.cache_stats["token_hits"] >= 1
    np.testing.assert_array_equal(out[r1], _solo(model, p1, 12))
    np.testing.assert_array_equal(out[r2], _solo(model, p2, 12))
    eng.assert_quiescent()


def test_engine_chunked_prefill_partial_reuse(model):
    """Long prompts (chunked prefill) diverging mid-block: the second
    request resumes from the token frontier, not the block floor."""
    rs = np.random.RandomState(14)
    base = rs.randint(0, 64, (18,))
    p1 = np.concatenate([base, rs.randint(0, 64, (2,))])
    p2 = np.concatenate([base, rs.randint(0, 64, (2,))])  # diverge @18
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=8,
                    max_seq_len=32)
    r1 = eng.add_request(Request(p1, max_new_tokens=4))
    out1 = eng.run()
    r2 = eng.add_request(Request(p2, max_new_tokens=4))
    out2 = eng.run()
    # 18 shared tokens = 4 full blocks + 2 COW tokens
    assert eng.mgr.cache_stats["token_hits"] >= 18
    assert eng.mgr.cache_stats["partial_hits"] >= 1
    np.testing.assert_array_equal(out1[r1], _solo(model, p1, 4))
    np.testing.assert_array_equal(out2[r2], _solo(model, p2, 4))
    eng.assert_quiescent()
