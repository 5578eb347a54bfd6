"""Multi-tenant batched LoRA serving (ISSUE 14).

* AdapterStore: strict registration, device-cache LRU eviction /
  hot-swap, pin exhaustion, pinned re-register refused
* null-adapter identity: an engine carrying an AdapterStore but serving
  only base requests is bit-exact with a storeless engine (which
  refuses an adapter request), and an adapter visibly changes the stream
* mixed continuous batch: every request's stream equals a dedicated
  single-adapter engine's — heterogeneous adapters batched through the
  grouped ragged path change nothing per-tenant
* cross-tenant isolation: the radix prefix cache never matches across
  adapter identities, even for byte-identical prompts
* fair admission: a saturating tenant cannot starve a light tenant
* ``serving.adapter_swap`` chaos: exception-atomic at the store, and a
  deferred admission is retried (not dropped) by the scheduler
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import RadixPrefixBlockManager
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.serving.adapters import AdapterStore
from paddle_tpu.serving.telemetry import (_ADAPTER_DEFERRALS,
                                          _ADAPTER_EVICTIONS)
from paddle_tpu.utils.faults import FAULTS, InjectedFault

ENG = dict(num_slots=3, block_size=4, max_prompt_len=16, max_seq_len=24)


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def make_adapter(model, seed, r=4):
    """A visible (non-zero-B) adapter state_dict on qkv/o projections."""
    import jax
    from paddle_tpu.peft import lora_init, lora_state_dict
    tree = lora_init(model, jax.random.PRNGKey(seed), r=r, alpha=8,
                     target_modules=("qkv_proj", "o_proj"))
    sd = lora_state_dict(tree)
    rs = np.random.RandomState(seed)
    for k in list(sd):
        if k.endswith(".lora_B"):
            sd[k] = rs.randn(*np.shape(sd[k])).astype(np.float32) * 0.05
    return sd


@pytest.fixture(scope="module")
def store(model):
    s = AdapterStore(model, capacity=2, max_rank=4)
    s.register("t1", make_adapter(model, 1))
    s.register("t2", make_adapter(model, 2, r=2))   # heterogeneous rank
    return s


def _run_one(model, store, prompt, n, adapter_id=None):
    eng = LLMEngine(model, adapter_store=store, **ENG)
    rid = eng.add_request(Request(prompt, max_new_tokens=n,
                                  adapter_id=adapter_id))
    out = eng.run()[rid]
    eng.assert_quiescent()
    return out


# ------------------------------------------------------------ store unit
def test_store_register_strict_and_known(model, store):
    assert store.known("t1") and store.known("t2")
    assert not store.known("nope")
    with pytest.raises(ValueError):
        store.register(None, make_adapter(model, 3))
    sd = make_adapter(model, 3)
    sd.pop(next(k for k in sd if k.endswith(".lora_A")))
    with pytest.raises(ValueError, match="missing"):
        AdapterStore(model, capacity=2, max_rank=4).register("bad", sd)
    sd2 = make_adapter(model, 3)
    sd2["totally.bogus.lora_A"] = np.zeros((1, 1), np.float32)
    with pytest.raises(ValueError, match="unexpected"):
        AdapterStore(model, capacity=2, max_rank=4).register("bad", sd2)


def test_store_rank_over_max_refused(model):
    s = AdapterStore(model, capacity=2, max_rank=2)
    with pytest.raises(ValueError):
        s.register("fat", make_adapter(model, 1, r=4))


def test_store_lru_eviction_and_hot_swap(model):
    s = AdapterStore(model, capacity=2, max_rank=4)
    for i in (1, 2, 3):
        s.register(f"a{i}", make_adapter(model, i))
    i1, i2 = s.ensure("a1"), s.ensure("a2")
    assert {i1, i2} == {0, 1}
    before = _ADAPTER_EVICTIONS.value()
    s.ensure("a1")                       # touch: a2 becomes LRU
    i3 = s.ensure("a3")                  # evicts a2, reuses its slot
    assert i3 == i2
    assert _ADAPTER_EVICTIONS.value() == before + 1
    assert s.index_of("a1") == i1        # survivor untouched
    with pytest.raises(KeyError):
        s.index_of("a2")                 # evicted: not resident
    assert s.ensure("a2") == i1          # re-upload evicts the new LRU (a1)


def test_store_pins_block_eviction_and_reregister(model):
    s = AdapterStore(model, capacity=1, max_rank=4)
    s.register("a1", make_adapter(model, 1))
    s.register("a2", make_adapter(model, 2))
    s.acquire("a1")
    with pytest.raises(RuntimeError, match="exhausted"):
        s.acquire("a2")                  # sole slot pinned
    with pytest.raises(ValueError, match="pinned"):
        s.register("a1", make_adapter(model, 5))   # pinned: no re-register
    s.release("a1")
    assert s.acquire("a2") == 0          # hot-swap into the freed slot
    s.release("a2")
    s.assert_quiescent()


# ----------------------------------------------------- engine: identity
def test_null_adapter_identity(model, store):
    p = np.arange(1, 6, dtype=np.int32)
    base_eng = LLMEngine(model, **ENG)       # adapter_store=None
    assert base_eng._lora_arg(np.zeros(2, np.int64), 1) is None
    rb = base_eng.add_request(Request(p, max_new_tokens=4))
    base = base_eng.run()[rb]
    # store attached, request base: bit-exact (lora arg never built)
    eng = LLMEngine(model, adapter_store=store, **ENG)
    assert eng._lora_arg(np.full(2, -1, np.int64), 1) is None
    assert _run_one(model, store, p, 4) == base
    # and an adapter request visibly changes the stream
    assert _run_one(model, store, p, 4, adapter_id="t1") != base


def test_mixed_batch_matches_dedicated_engines(model, store):
    """Base + two heterogeneous adapters in ONE continuous batch emit
    exactly what three dedicated engines emit (radix cache active)."""
    p = np.arange(1, 6, dtype=np.int32)
    eng = LLMEngine(model, adapter_store=store, **ENG)
    r0 = eng.add_request(Request(p, max_new_tokens=4))
    r1 = eng.add_request(Request(p, max_new_tokens=4, adapter_id="t1",
                                 tenant_id="a"))
    r2 = eng.add_request(Request(p, max_new_tokens=4, adapter_id="t2",
                                 tenant_id="b"))
    out = eng.run()
    eng.assert_quiescent()
    store.assert_quiescent()
    assert out[r0] == _run_one(model, None, p, 4)
    assert out[r1] == _run_one(model, store, p, 4, adapter_id="t1")
    assert out[r2] == _run_one(model, store, p, 4, adapter_id="t2")
    assert out[r1] != out[r0] and out[r2] != out[r0]
    assert out[r1] != out[r2]


# ------------------------------------------------- cross-tenant isolation
def test_radix_never_matches_across_adapters():
    mgr = RadixPrefixBlockManager(num_blocks=8, block_size=4)
    toks = np.arange(10, dtype=np.int32)
    mgr.allocate(1, 10)
    mgr.commit_prefix(1, toks, adapter="t1")
    assert mgr.match_prefix(toks, adapter="t1").token_count > 0
    assert mgr.match_prefix(toks, adapter="t2").token_count == 0
    assert mgr.match_prefix(toks).token_count == 0          # base trie
    mgr.free(1)


def test_same_prompt_sequential_tenants_no_contamination(model, store):
    """Byte-identical prompts under different adapters, served one after
    another through the SAME engine (t1's blocks are parked in the radix
    cache when t2 arrives) — each stream still equals its dedicated
    engine, and the base request is untouched by either."""
    p = np.arange(2, 9, dtype=np.int32)
    eng = LLMEngine(model, adapter_store=store, **ENG)
    outs = {}
    for aid in ("t1", "t2", None, "t1"):
        rid = eng.add_request(Request(p, max_new_tokens=4, adapter_id=aid))
        outs[(aid, rid)] = eng.run()[rid]
    eng.assert_quiescent()
    for (aid, _), got in outs.items():
        assert got == _run_one(model, store, p, 4, adapter_id=aid), aid


# --------------------------------------------------------- fair admission
def test_fair_admission_light_tenant_not_starved(model):
    """One slot, four queued requests from a saturating tenant plus one
    from a light tenant enqueued LAST. Deficit-weighted admission serves
    the light tenant well before the heavy backlog drains (pure FCFS
    would serve it dead last)."""
    order = []

    def track(req, tok):
        if len(req.tokens) == 1:
            order.append(req.tenant_id)

    eng = LLMEngine(model, num_slots=1, block_size=4, max_prompt_len=16,
                    max_seq_len=24)
    for i in range(4):
        eng.add_request(Request(np.arange(1 + i, 6 + i, dtype=np.int32),
                                max_new_tokens=3, tenant_id="heavy",
                                stream=track))
    eng.add_request(Request(np.arange(9, 14, dtype=np.int32),
                            max_new_tokens=3, tenant_id="light",
                            stream=track))
    eng.run()
    eng.assert_quiescent()
    assert len(order) == 5
    assert order.index("light") <= 2, order    # FCFS would put it at 4
    assert order[-1] == "heavy"


def test_tenant_weight_validation(model):
    eng = LLMEngine(model, **ENG)
    eng.sched.set_tenant_weight("gold", 4.0)
    assert eng.sched.tenant_weights["gold"] == 4.0
    with pytest.raises(ValueError):
        eng.sched.set_tenant_weight("bad", 0.0)


# ------------------------------------------------------------------ chaos
def test_adapter_swap_fault_is_exception_atomic(model):
    s = AdapterStore(model, capacity=2, max_rank=4)
    s.register("a1", make_adapter(model, 1))
    with FAULTS.scope("serving.adapter_swap", exc=InjectedFault):
        with pytest.raises(InjectedFault):
            s.ensure("a1")
        assert "a1" not in s._resident   # host copy stays, no residency
        assert len(s._free) == 2         # no slot leaked
    idx = s.ensure("a1")                 # clean retry succeeds
    assert idx in (0, 1)
    s.assert_quiescent()


def test_adapter_swap_fault_defers_admission_then_retries(model):
    """A one-shot upload fault makes the scheduler defer the admission;
    the next tick retries and the request completes with the exact
    no-fault stream (nothing dropped, nothing leaked)."""
    p = np.arange(3, 10, dtype=np.int32)
    s = AdapterStore(model, capacity=2, max_rank=4)
    s.register("t1", make_adapter(model, 1))
    want = _run_one(model, s, p, 4, adapter_id="t1")

    s2 = AdapterStore(model, capacity=2, max_rank=4)
    s2.register("t1", make_adapter(model, 1))
    eng = LLMEngine(model, adapter_store=s2, **ENG)
    before = _ADAPTER_DEFERRALS.value()
    with FAULTS.scope("serving.adapter_swap", exc=InjectedFault, on={0}):
        rid = eng.add_request(Request(p, max_new_tokens=4,
                                      adapter_id="t1"))
        out = eng.run()
    assert out[rid] == want
    assert _ADAPTER_DEFERRALS.value() == before + 1
    eng.assert_quiescent()
    s2.assert_quiescent()


# -------------------------------------------------------------- intake
def test_add_request_validates_adapter(model, store):
    p = np.arange(1, 5, dtype=np.int32)
    eng = LLMEngine(model, adapter_store=store, **ENG)
    with pytest.raises(ValueError):
        eng.add_request(Request(p, adapter_id="unregistered"))
    no_store = LLMEngine(model, **ENG)
    with pytest.raises(ValueError):
        no_store.add_request(Request(p, adapter_id="t1"))
