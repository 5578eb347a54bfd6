"""Context-parallel long-context serving (ISSUE 18): the sequence-sharded
paged KV pool, ring/Ulysses-merged chunked prefill, and psum-merged
cross-shard decode.

The contract under test is BIT-IDENTITY: a cp>1 engine must emit exactly
the tokens its cp=1 twin emits — through plain decode, chunked prefill,
speculative decoding, preemption/replay, radix prefix reuse, and int8 KV
pools — because every shard_map'd program merges per-shard online-softmax
partials into the same replicated result the single-device program
computes. Plus: the ``cp=1`` single-device engine, the ``too_long`` graceful
admission rejection, the ``serving.cp_gather`` chaos site's
exception-atomicity, cp-scaled admission capacity, the cp metric gauges,
and the roofline merge-traffic term.

CPU-safe: conftest forces an 8-device virtual mesh.
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.mesh import HybridMesh
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import clear_jit_caches
from paddle_tpu.observability.metrics import METRICS
from paddle_tpu.observability.roofline import ModelGeometry, phase_bytes
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.utils.faults import FAULTS, InjectedFault


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64, dtype=jnp.float32)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def draft(model):
    pt.seed(1)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64, dtype=jnp.float32)
    return LlamaForCausalLM(cfg)


def _mk(model, **kw):
    args = dict(num_slots=2, block_size=4, max_prompt_len=16,
                max_seq_len=48)
    args.update(kw)
    return LLMEngine(model, **args)


def _prompts(n, rs, lo=3, hi=14, vocab=64):
    return [rs.randint(1, vocab, (int(l),))
            for l in rs.randint(lo, hi, size=n)]


def _run(model, prompts, max_new=6, **ekw):
    eng = _mk(model, **ekw)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=max_new))
    out = {rid: list(map(int, t)) for rid, t in eng.run().items()}
    eng.assert_quiescent()
    return out, eng


# ------------------------------------------------------------ mesh axis

def test_hybrid_mesh_cp_axis():
    m = HybridMesh(cp=2, devices=__import__("jax").devices()[:2])
    assert m.cp == 2 and m.size("cp") == 2
    assert "cp" in m.axis_names


# ------------------------------------------------- greedy identity suite

@pytest.mark.parametrize("cp", [2, 4])
def test_greedy_identity_plain_decode(model, cp):
    rs = np.random.RandomState(0)
    prompts = _prompts(3, rs)
    ref, _ = _run(model, prompts)
    got, eng = _run(model, prompts, cp=cp)
    assert eng.cp == cp and eng.exe.mesh is not None
    assert got == ref


@pytest.mark.parametrize("cp", [2, 4])
def test_greedy_identity_chunked_prefill(model, cp):
    """Prompts longer than max_prompt_len ride the shard_map'd chunked
    prefill whose per-shard partials merge via the ring rotation."""
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, 64, (30,)), rs.randint(1, 64, (21,))]
    ref, _ = _run(model, prompts)
    got, _ = _run(model, prompts, cp=cp)
    assert got == ref


def test_greedy_identity_ulysses_merge(model, monkeypatch):
    """PT_CP_IMPL=ulysses swaps the chunk merge for the tiled
    all_to_all; heads (4) divide by cp (2) so it is eligible — and the
    tokens must still match cp=1 exactly."""
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 64, (26,))]
    ref, _ = _run(model, prompts)
    monkeypatch.setenv("PT_CP_IMPL", "ulysses")
    got, _ = _run(model, prompts, cp=2)
    assert got == ref


def test_greedy_identity_spec_decode(model, draft):
    """Draft-and-verify under cp: the target verify chunk runs sharded
    with merged partials, the rewind runs through the cp jit."""
    rs = np.random.RandomState(3)
    prompts = _prompts(3, rs)
    ref, re = _run(model, prompts, max_new=8, draft_model=draft)
    got, ge = _run(model, prompts, max_new=8, draft_model=draft, cp=2)
    assert ge.stats["spec_ticks"] > 0          # speculation actually ran
    assert got == ref
    assert ge.stats["spec_accepted"] == re.stats["spec_accepted"]


def test_greedy_identity_preempt_replay(model):
    """A starved pool forces preempt + replay (chunked re-prefill of
    prompt+generated) — identical tokens to the cp=1 twin under the
    same pressure."""
    rs = np.random.RandomState(4)
    prompts = [rs.randint(1, 64, (int(n),)) for n in (10, 12, 8)]
    kw = dict(num_slots=3, num_blocks=18, preemption=True,
              prefix_caching=False)
    ref, re = _run(model, prompts, **kw)
    got, ge = _run(model, prompts, cp=2, **kw)
    assert got == ref


def test_greedy_identity_radix_prefix_reuse(model):
    """Shared prompt prefixes adopt trie blocks by reference; the
    boundary-block COW copy crosses shards via the gather-psum-scatter
    program and tokens still match."""
    rs = np.random.RandomState(5)
    base = rs.randint(1, 64, (9,)).tolist()
    prompts = [base + [7], base + [11, 13], base[:6] + [3, 2]]

    def seq(cp):
        eng = _mk(model, num_slots=2, cp=cp)
        out = {}
        for p in prompts:                      # sequential → trie reuse
            rid = eng.add_request(Request(p, max_new_tokens=6))
            while not eng.requests[rid].done:
                eng.step()
            out[rid] = list(map(int, eng.requests[rid].tokens))
        eng.assert_quiescent()
        return out, eng

    ref, re = seq(1)
    got, ge = seq(2)
    assert got == ref
    stats = ge.mgr.cache_stats
    assert stats.get("token_hits", 0) + stats.get("hit_blocks", 0) > 0


def test_greedy_identity_int8_kv(model):
    """int8 KV pools shard alongside the codes: per-position scale pools
    carry P('cp') too, and quantize-on-write lands each chunk's K/V in
    the owning shard."""
    rs = np.random.RandomState(6)
    prompts = _prompts(3, rs)
    ref, _ = _run(model, prompts, kv_dtype="int8")
    got, eng = _run(model, prompts, kv_dtype="int8", cp=2)
    assert got == ref
    assert eng.cache.k_scales                 # quantized pool actually on


# ------------------------------------------------------------- cp == 1

def test_cp1_engine_unchanged(model):
    """cp=1 must not build a mesh, shard anything, or register shard
    gauges — bit-identical to the pre-cp engine."""
    eng = _mk(model, cp=1)
    assert eng.cp == 1 and eng.exe.cp == 1 and eng.exe.mesh is None
    assert not hasattr(eng.exe, "_cp_tick")


# ------------------------------------------------- admission: too_long

def test_too_long_finishes_gracefully_instead_of_wedging(model):
    """A prompt whose worst case exceeds the whole pool must come back
    finished with finish_reason='too_long' — not raise, not sit at the
    FCFS head starving everyone behind it."""
    eng = _mk(model, num_blocks=4)
    rs = np.random.RandomState(8)
    rid = eng.add_request(Request(rs.randint(1, 64, (30,)),
                                  max_new_tokens=8))
    req = eng.requests[rid]
    assert req.done and req.finish_reason == "too_long"
    assert not eng.queue                       # never occupies the queue
    # the engine still serves a normal request afterwards
    rid2 = eng.add_request(Request([1, 2, 3], max_new_tokens=3))
    out = eng.run()
    assert len(out[rid2]) == 3
    eng.assert_quiescent()
    assert eng.stats["rejected"] >= 1


def test_admissible_length_scales_with_cp(model):
    """The point of cp: each shard holds num_blocks/cp physical blocks,
    so a cp-wide pool admits ~cp× the prompt length a single device
    holds. num_blocks scales with cp; the boundary prompt that finishes
    'too_long' at cp=1 admits at cp=2."""
    long_p = list(np.random.RandomState(9).randint(1, 64, (40,)))
    small = _mk(model, num_blocks=8, max_seq_len=64)       # 32 positions
    rid = small.add_request(Request(long_p, max_new_tokens=4))
    assert small.requests[rid].finish_reason == "too_long"
    big = _mk(model, num_blocks=16, max_seq_len=64, cp=2)  # 64 positions
    rid = big.add_request(Request(long_p, max_new_tokens=4))
    assert not big.requests[rid].done          # admitted, queued
    out = big.run()
    assert len(out[rid]) == 4
    big.assert_quiescent()
    # per-shard footprint: 8 blocks each, the small engine's whole pool
    assert int(np.asarray(big.cache.k_pools[0]).shape[0]) == 16


def test_num_blocks_rounds_up_to_cp_multiple(model):
    eng = _mk(model, num_blocks=9, cp=2)
    assert eng.mgr.num_blocks == 10


# ------------------------------------------------- punted combinations

def test_cp_refuses_beams_lora_and_handoff(model):
    eng = _mk(model, cp=2)
    with pytest.raises(NotImplementedError, match="beam"):
        eng.add_request(Request([1, 2, 3], max_new_tokens=2, num_beams=2))
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.extract_sequence(0)
    from paddle_tpu.serving.adapters import AdapterStore
    with pytest.raises(NotImplementedError, match="LoRA"):
        _mk(model, cp=2, adapter_store=AdapterStore(model))


# ---------------------------------------------- serving.cp_gather chaos

def test_chaos_cp_gather_exception_atomic(model):
    """An injected cp_gather fault fires BEFORE table growth and the
    donating tick jit: the tick aborts with cache/tables/ledger
    untouched, no blocks leak, the run still finishes with the clean
    run's exact tokens, and the fleet ends quiescent + reconciled."""
    rs = np.random.RandomState(10)
    prompts = _prompts(3, rs)
    ref, _ = _run(model, prompts, cp=2)
    eng = _mk(model, cp=2)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=6))
    fired = 0
    with FAULTS.scope("serving.cp_gather", on={1, 3}, exc=InjectedFault):
        while eng.has_work():
            try:
                eng.step()
            except InjectedFault:
                fired += 1
    assert fired == 2
    out = {r: list(map(int, req.tokens))
           for r, req in eng.pop_finished().items()}
    assert out == ref
    eng.assert_quiescent()
    assert eng.kv.reconcile()["ok"]


def test_cp_gather_site_only_arms_above_cp1(model):
    rs = np.random.RandomState(11)
    eng = _mk(model)                           # cp=1: site never fires
    eng.add_request(Request(rs.randint(1, 64, (5,)), max_new_tokens=4))
    with FAULTS.scope("serving.cp_gather", exc=InjectedFault):
        eng.run()
    eng.assert_quiescent()
    assert FAULTS.hits["serving.cp_gather"] == 0
    FAULTS.clear()


# ----------------------------------------------------- metrics + roofline

def test_cp_gauges_and_gather_histogram(model):
    rs = np.random.RandomState(12)
    _run(model, _prompts(2, rs), cp=2)
    assert METRICS.get("serving_cp_axis_size").value() == 2
    assert METRICS.get("serving_cp_gather_seconds").value()["count"] > 0
    per_shard = METRICS.get("serving_cp_shard_blocks")
    assert per_shard.value(shard="0") >= 0


def test_shard_occupancy_buckets_contiguous_split():
    from paddle_tpu.serving.cp import shard_occupancy
    assert shard_occupancy([0, 1, 7, 8, 15], 16, 2) == [3, 2]
    assert shard_occupancy([], 16, 4) == [0, 0, 0, 0]


def test_roofline_bills_cp_merge_traffic(model):
    g1 = ModelGeometry.from_config(model.cfg, dtype_bytes=4)
    from dataclasses import replace
    g2 = replace(g1, cp=2)
    b1 = phase_bytes(g1, tokens=64, weight_passes=1, kv_read_positions=640)
    b2 = phase_bytes(g2, tokens=64, weight_passes=1, kv_read_positions=640)
    extra = 64 * g1.num_layers * g1.heads * (g1.head_dim + 2) * 4.0 * 0.5 * 2
    assert b2 == pytest.approx(b1 + extra)
