"""Quantized serving subsystem (ISSUE 17): weight-only int8/int4 via
``quantize_for_serving`` (dense + MoE expert stacks + SmoothQuant fold),
the int8 paged KV cache with per-(position, kv-head) scale pools —
kernel-level dequant parity, engine greedy identity, radix/COW
semantics, the cross-replica extract→ship→install wire with sealed
scale checksums, the trace-time breadcrumbs that tell an int8 program
from a ``kv_dtype=None`` one, the ``serving.kv_quant`` chaos site's
exception-atomicity, and the actual-dtype bytes fixes in
``cache_block_bytes`` / roofline ``ModelGeometry``."""
import copy

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from paddle_tpu.models.paged import PagedKVCache, clear_jit_caches
from paddle_tpu.observability.metrics import METRICS
from paddle_tpu.observability.roofline import (ModelGeometry,
                                               kv_bytes_per_position,
                                               weight_bytes)
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.quantization import QuantizedWeight
from paddle_tpu.serving import LLMEngine, Replica, Request, Router
from paddle_tpu.serving.kv import cache_block_bytes
from paddle_tpu.serving.quant import (QuantizedExpertStack,
                                      expert_stack_quantize, quant_quality,
                                      quantize_for_serving,
                                      quantized_weight_bytes,
                                      smooth_for_serving)
from paddle_tpu.serving.transfer import (DeviceKVTransfer, KVTransferError,
                                         validate_payload)
from paddle_tpu.utils.faults import FAULTS, InjectedFault


@pytest.fixture(scope="module", autouse=True)
def _preserve_global_rng():
    from paddle_tpu.core import random as _prng
    saved = None if _prng._global is None else _prng._global.key
    yield
    if saved is None:
        _prng._global = None
    else:
        pt.seed(0)
        _prng._global.key = saved


@pytest.fixture(autouse=True)
def _fresh_jits():
    # the breadcrumb tests read what a program traced: none may inherit
    # (or leak) programs another test traced
    clear_jit_caches()
    yield
    clear_jit_caches()


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
    args = dict(num_slots=4, block_size=4, max_prompt_len=16,
                max_seq_len=48)
    args.update(kw)
    return LLMEngine(model, **args)


def _prompts(n, rs, lo=3, hi=14, vocab=64):
    return [rs.randint(1, vocab, (int(l),))
            for l in rs.randint(lo, hi, size=n)]


def _run(model, prompts, max_new=8, **ekw):
    eng = _mk(model, **ekw)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=max_new))
    out = {rid: list(map(int, t)) for rid, t in eng.run().items()}
    eng.assert_quiescent()
    return out, eng


def _match_rate(a, b):
    pairs = [(x, y) for rid in a for x, y in zip(a[rid], b[rid])]
    return float(np.mean([x == y for x, y in pairs]))


# ------------------------------------------------- kernel dequant parity

def _quantize_pool(rng, n, bs, h_kv, d):
    f = rng.normal(size=(n, bs, h_kv, d)).astype(np.float32)
    scale = np.maximum(np.abs(f).max(axis=-1), 1e-8) / 127.0
    q = np.clip(np.round(f / scale[..., None]), -127, 127).astype(np.int8)
    deq = q.astype(np.float32) * scale[..., None]
    return jnp.asarray(q), jnp.asarray(scale), jnp.asarray(deq)


def test_decode_parity_quantized_pool():
    """Pallas-interpret and XLA decode over an int8 pool must both equal
    the f32 reference run over the dequantized pool."""
    rng = np.random.default_rng(0)
    b, h, h_kv, d, bs, mb, n = 3, 4, 2, 16, 8, 4, 24
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kq, ks, kd = _quantize_pool(rng, n, bs, h_kv, d)
    vq, vs, vd = _quantize_pool(rng, n, bs, h_kv, d)
    tables = np.full((b, mb), n, np.int32)
    lens = np.asarray([9, 17, 4], np.int32)
    for i in range(b):
        need = -(-int(lens[i]) // bs)
        tables[i, :need] = rng.choice(n, size=need, replace=False)
    tables = jnp.asarray(tables)
    ref = pa.paged_decode_attention_xla(q, kd, vd, tables, lens)
    out_x = pa.paged_decode_attention_xla(q, kq, vq, tables, lens,
                                          k_scale=ks, v_scale=vs)
    out_p = pa.paged_decode_attention_pallas(q, kq, vq, tables, lens,
                                             k_scale=ks, v_scale=vs,
                                             interpret=True)
    assert np.abs(np.asarray(out_x) - np.asarray(ref)).max() < 2e-5
    assert np.abs(np.asarray(out_p) - np.asarray(ref)).max() < 2e-5


def test_chunk_parity_quantized_pool():
    rng = np.random.default_rng(1)
    a, c, h, h_kv, d, bs, mb, n = 2, 5, 4, 2, 16, 8, 5, 24
    q = jnp.asarray(rng.normal(size=(a, c, h, d)), jnp.float32)
    kq, ks, kd = _quantize_pool(rng, n, bs, h_kv, d)
    vq, vs, vd = _quantize_pool(rng, n, bs, h_kv, d)
    offs = np.asarray([3, 11], np.int32)
    cls = np.asarray([5, 4], np.int32)
    tables = np.full((a, mb), n, np.int32)
    for i in range(a):
        need = -(-int(offs[i] + cls[i]) // bs)
        tables[i, :need] = rng.choice(n, size=need, replace=False)
    tables = jnp.asarray(tables)
    ref = pa.paged_chunk_attention_xla(q, kd, vd, tables, offs, cls)
    out_x = pa.paged_chunk_attention_xla(q, kq, vq, tables, offs, cls,
                                         k_scale=ks, v_scale=vs)
    out_p = pa.paged_chunk_attention_pallas(q, kq, vq, tables, offs, cls,
                                            k_scale=ks, v_scale=vs,
                                            interpret=True)
    for i, cl in enumerate(cls):
        assert np.abs(np.asarray(out_x)[i, :cl]
                      - np.asarray(ref)[i, :cl]).max() < 2e-5
        assert np.abs(np.asarray(out_p)[i, :cl]
                      - np.asarray(ref)[i, :cl]).max() < 2e-5


# ---------------------------------------------------------- cache init

def test_cache_init_int8_geometry():
    c = PagedKVCache.init(2, 8, 4, 2, 16, 3, 4, jnp.float32,
                          kv_dtype="int8")
    assert all(p.dtype == jnp.int8 for p in (*c.k_pools, *c.v_pools))
    assert len(c.k_scales) == 2 and len(c.v_scales) == 2
    # one f32 scale per (block, position, kv-head)
    assert c.k_scales[0].shape == (8, 4, 2)
    assert c.k_scales[0].dtype == jnp.float32


def test_cache_init_rejects_unsupported_kv_dtype():
    with pytest.raises(ValueError):
        PagedKVCache.init(2, 8, 4, 2, 16, 3, 4, jnp.float32,
                          kv_dtype="int4")


def test_cache_block_bytes_halves_at_real_head_dim():
    """At head_dim 64 the int8 pool (1 B codes + 4 B per-head scale) is
    ~0.53x the bf16 pool — the capacity win the subsystem exists for."""
    bf16 = PagedKVCache.init(2, 8, 16, 2, 64, 3, 4, jnp.bfloat16)
    int8 = PagedKVCache.init(2, 8, 16, 2, 64, 3, 4, jnp.bfloat16,
                             kv_dtype="int8")
    ratio = cache_block_bytes(int8) / cache_block_bytes(bf16)
    assert ratio <= 0.55, ratio


# ----------------------------------------------- engine greedy identity

def test_int8_kv_engine_matches_bf16_greedy(model):
    rs = np.random.RandomState(0)
    prompts = _prompts(6, rs)
    ref, _ = _run(model, prompts)
    out, eng = _run(model, prompts, kv_dtype="int8")
    assert eng.cache.k_scales and eng.cache.k_pools[0].dtype == jnp.int8
    # tiny random models have near-tied logits; on real checkpoints the
    # bench asserts >= 0.95 — here the fixed seed gives a high floor
    assert _match_rate(ref, out) >= 0.85


def test_quantize_for_serving_publishes_what_it_did(model):
    """The call to ``quantize_for_serving`` is the one decision to
    quantize weights: a model that never passes through it serves its
    own, and the call reports bits, layers and bytes on its gauges."""
    g = lambda name: METRICS.get(name).value()
    assert getattr(model, "_wo_bits", None) is None
    assert g("serving_quant_weight_bits") == 0       # nothing quantized yet
    q = quantize_for_serving(copy.deepcopy(model), "weight_only_int4")
    assert q._wo_bits == 4
    assert isinstance(q.model.layers[0].self_attn.qkv_proj, QuantizedWeight)
    assert not isinstance(model.model.layers[0].self_attn.qkv_proj,
                          QuantizedWeight)
    assert g("serving_quant_weight_bits") == 4
    assert g("serving_quant_layers") == len(model.model.layers)
    assert g("serving_quant_smoothed") == 0
    assert g("serving_quant_weight_bytes") == quantized_weight_bytes(q) > 0


def test_full_quant_stack_spec_chunked_prefill(model, draft):
    """int8 KV + int8 weights under the FULL engine — spec decode and a
    chunked-prefill prompt — runs to completion, stays quiescent, and
    tracks the bf16 greedy stream."""
    rs = np.random.RandomState(2)
    prompts = _prompts(3, rs) + [rs.randint(1, 64, (21,))]
    ref, _ = _run(model, prompts, max_prompt_len=8, draft_model=draft)
    qm = quantize_for_serving(copy.deepcopy(model), "weight_only_int8")
    out, eng = _run(qm, prompts, max_prompt_len=8, draft_model=draft,
                    kv_dtype="int8")
    assert all(len(t) == 8 for t in out.values())
    assert _match_rate(ref, out) >= 0.7


def test_preempt_replay_under_int8(model):
    """Preemption + resume-replay re-prefills through the quantized
    scatter path; the engine must finish cleanly and stay quiescent."""
    rs = np.random.RandomState(3)
    prompts = _prompts(6, rs, lo=6, hi=12)
    out, eng = _run(model, prompts, kv_dtype="int8", num_slots=2,
                    num_blocks=14, preemption=True, max_seq_len=24)
    assert all(len(t) == 8 for t in out.values())


# ------------------------------------------------- radix/COW semantics

def test_prefix_cache_partial_boundary_cow_int8(model):
    """Shared prefix diverging MID-block: the radix trie COW-copies the
    partial block — codes AND scale rows — so cached and uncached int8
    engines emit identical tokens."""
    rs = np.random.RandomState(4)
    base = rs.randint(1, 64, (10,))           # 2.5 blocks at block_size 4
    prompts = [base,
               np.concatenate([base[:6], rs.randint(1, 64, (5,))]),
               np.concatenate([base[:9], rs.randint(1, 64, (3,))])]
    plain, _ = _run(model, prompts, kv_dtype="int8", prefix_caching=False)
    cached, eng = _run(model, prompts, kv_dtype="int8",
                       prefix_caching=True)
    assert cached == plain
    assert eng.kv.reconcile()["ok"]


def test_prefix_adopt_evict_refcounts_int8(model):
    """Sequential same-prefix requests adopt parked blocks (refcounts on
    the int8 pool + scale rows), evictions reclaim them, and the ledger
    reconciles block-for-block."""
    rs = np.random.RandomState(5)
    base = rs.randint(1, 64, (8,))
    eng = _mk(model, kv_dtype="int8", num_blocks=24)
    for i in range(3):                        # sequential: adopt each time
        eng.add_request(Request(base, max_new_tokens=6, req_id=i))
        eng.run()
    stats = eng.mgr.cache_stats
    assert stats.get("hit_blocks", 0) + stats.get("token_hits", 0) > 0
    eng.assert_quiescent()
    assert eng.kv.reconcile()["ok"]


def test_beam_search_int8_cow(model):
    """Beam fork + partial-block COW over the int8 pool (codes + scales
    forked together)."""
    rs = np.random.RandomState(6)
    p = rs.randint(1, 64, (7,))
    ref, _ = _run(model, [p], max_new=6)
    eng = _mk(model, kv_dtype="int8")
    eng.add_request(Request(p, max_new_tokens=6, num_beams=2))
    out = eng.run()
    assert len(list(out.values())[0]) == 6
    eng.assert_quiescent()


# -------------------------------------------- cross-replica handoff

def test_disaggregated_int8_matches_single_engine(model):
    """Every sequence crosses extract→ship→install with int8 codes and
    scale rows sealed + checksummed; fleet output == single int8
    engine, token for token."""
    rs = np.random.RandomState(7)
    prompts = _prompts(4, rs) + [rs.randint(1, 64, (19,))]
    ref, _ = _run(model, prompts, max_prompt_len=8, kv_dtype="int8")
    r = Router([Replica(_mk(model, max_prompt_len=8, kv_dtype="int8"),
                        role="prefill"),
                Replica(_mk(model, max_prompt_len=8, kv_dtype="int8"),
                        role="decode")])
    for p in prompts:
        r.add_request(Request(p, max_new_tokens=8))
    out = {rid: list(map(int, t)) for rid, t in r.run().items()}
    assert out == ref
    r.assert_quiescent()
    assert r.stats["transfers"] == 5


def _extract_one(model, prompt, **kw):
    src = _mk(model, prefill_only=True, **kw)
    src.add_request(Request(prompt, max_new_tokens=6, req_id=0))
    while 0 not in [int(x) for x in src.slot_req] or not src.active.any():
        src.step()
    return src, src.extract_sequence(0)


def test_payload_seal_covers_scales(model):
    rs = np.random.RandomState(8)
    src, payload = _extract_one(model, rs.randint(1, 64, (9,)),
                                kv_dtype="int8")
    assert payload.k_scale is not None and payload.expect["quant"]
    assert {"kssum", "vssum"} <= set(payload.expect)
    dst = _mk(model, kv_dtype="int8")
    validate_payload(DeviceKVTransfer().ship(payload, dst), dst)
    assert dst.install_sequence(payload)
    out = {rid: list(map(int, t)) for rid, t in dst.run().items()}
    assert len(out[0]) == 6
    src.assert_quiescent()
    dst.assert_quiescent()


def test_corrupted_scale_rejected(model):
    rs = np.random.RandomState(9)
    _, payload = _extract_one(model, rs.randint(1, 64, (9,)),
                              kv_dtype="int8")
    dst = _mk(model, kv_dtype="int8")
    payload.k_scale = payload.k_scale * 2.0       # silent rescale attempt
    with pytest.raises(KVTransferError, match="k-scale-checksum"):
        validate_payload(payload, dst)


def test_kv_dtype_mismatch_rejected(model):
    rs = np.random.RandomState(10)
    _, qpayload = _extract_one(model, rs.randint(1, 64, (9,)),
                               kv_dtype="int8")
    bf16_dst = _mk(model)
    with pytest.raises(KVTransferError, match="dtype mismatch"):
        validate_payload(qpayload, bf16_dst)
    with pytest.raises(ValueError, match="quantization"):
        bf16_dst.install_sequence(qpayload)
    _, bpayload = _extract_one(model, rs.randint(1, 64, (9,)))
    int8_dst = _mk(model, kv_dtype="int8")
    with pytest.raises(KVTransferError, match="dtype mismatch"):
        validate_payload(bpayload, int8_dst)


# ------------------------------------------------ trace-time breadcrumbs

def test_int8_programs_trace_once_and_say_so(model):
    """An int8 pool's programs leave the quantize-on-write and
    dequantize-on-read breadcrumbs when they trace, and a second request
    of the same shapes traces nothing again."""
    rs = np.random.RandomState(11)
    eng = _mk(model, kv_dtype="int8")
    pa._trace_events.clear()
    eng.add_request(Request(rs.randint(1, 64, (5,)), max_new_tokens=4))
    eng.run()
    assert "kv:int8-write" in pa._trace_events     # quantized scatter
    assert "decode:int8-kv" in pa._trace_events    # dequant-on-read

    pa._trace_events.clear()
    eng.add_request(Request(rs.randint(1, 64, (5,)), max_new_tokens=4))
    eng.run()                       # cached traces: still the int8 path
    assert "kv:int8-write" not in pa._trace_events  # no retrace happened
    eng.assert_quiescent()


def test_bf16_traces_carry_no_quant_breadcrumbs(model):
    """``kv_dtype=None`` (the default): model-dtype pools, no scale
    pools, and no program of it traces a quantized branch."""
    rs = np.random.RandomState(12)
    pa._trace_events.clear()
    _, eng = _run(model, _prompts(2, rs), kv_dtype=None)
    assert not eng.cache.k_scales          # no scale pools
    assert eng.cache.k_pools[0].dtype == model.cfg.dtype
    assert pa._trace_events
    assert not any("int8" in e for e in pa._trace_events)


# ------------------------------------------------- serving.kv_quant chaos

def test_chaos_kv_quant_exception_atomic(model):
    """An injected kv_quant fault must abort the tick BEFORE the
    quantize-on-write scatter: the engine survives, no blocks leak, no
    stale scale rows land, and the finished tokens match a clean run."""
    rs = np.random.RandomState(13)
    prompts = _prompts(3, rs)
    ref, _ = _run(model, prompts, kv_dtype="int8")
    eng = _mk(model, kv_dtype="int8")
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=8))
    fired = 0
    with FAULTS.scope("serving.kv_quant", on={1}, exc=InjectedFault):
        while eng.has_work():
            try:
                eng.step()
            except InjectedFault:
                fired += 1
    assert fired == 1
    out = {r: list(map(int, req.tokens))
           for r, req in eng.pop_finished().items()}
    assert out == ref
    eng.assert_quiescent()
    assert eng.kv.reconcile()["ok"]


def test_kv_quant_site_only_fires_for_int8_pools(model):
    rs = np.random.RandomState(14)
    eng = _mk(model)                      # bf16 pool: site never armed
    eng.add_request(Request(rs.randint(1, 64, (5,)), max_new_tokens=4))
    with FAULTS.scope("serving.kv_quant", exc=InjectedFault):
        eng.run()
    eng.assert_quiescent()
    assert FAULTS.hits["serving.kv_quant"] == 0
    FAULTS.clear()


# ------------------------------------------------ quantize_for_serving

def test_weight_only_roundtrip_and_quality(model):
    rs = np.random.RandomState(15)
    ids = jnp.asarray(rs.randint(1, 64, size=(2, 10)))
    ref = np.asarray(model(ids))
    m8 = quantize_for_serving(copy.deepcopy(model), "weight_only_int8")
    m4 = quantize_for_serving(copy.deepcopy(model), "weight_only_int4")
    assert m8._wo_bits == 8 and m4._wo_bits == 4
    att = m8.model.layers[0].self_attn
    assert isinstance(att.qkv_proj, QuantizedWeight)
    q8 = quant_quality(ref, m8(ids))
    q4 = quant_quality(ref, m4(ids))
    assert q8["logit_mse"] < q4["logit_mse"]       # int8 strictly tighter
    assert q8["greedy_match_rate"] >= 0.9
    assert METRICS.get("serving_quant_logit_mse").value() == \
        q4["logit_mse"]


def test_gptq_for_serving(model):
    rs = np.random.RandomState(16)
    ids = jnp.asarray(rs.randint(1, 64, size=(2, 12)))
    m = quantize_for_serving(copy.deepcopy(model), "gptq_int4",
                             calib_ids=ids)
    assert m._wo_bits == 4
    assert isinstance(m.model.layers[0].self_attn.qkv_proj,
                      QuantizedWeight)


def test_smooth_fold_is_function_preserving(model):
    rs = np.random.RandomState(17)
    ids = jnp.asarray(rs.randint(1, 64, size=(2, 10)))
    ref = np.asarray(model(ids))
    for kw in ({}, {"calib_ids": ids}):
        sm = smooth_for_serving(copy.deepcopy(model), **kw)
        assert np.abs(np.asarray(sm(ids)) - ref).max() < 1e-4


def test_quantize_moe_expert_stacks():
    pt.seed(3)
    mm = MixtralForCausalLM(MixtralConfig.tiny())
    rs = np.random.RandomState(18)
    ids = jnp.asarray(rs.randint(1, mm.cfg.vocab_size, size=(2, 8)))
    ref = np.asarray(mm(ids))
    mq = quantize_for_serving(copy.deepcopy(mm), "weight_only_int8",
                              smooth=True)
    ex = mq.layers[0].moe.experts
    assert isinstance(ex.gate_up, QuantizedExpertStack)
    assert ex.gate_up.q.dtype == jnp.int8
    assert mq.layers[0].moe.gate_w.dtype == jnp.float32  # router: never
    q = quant_quality(ref, mq(ids))
    assert q["greedy_match_rate"] >= 0.75
    # the quantized MoE also serves through the paged engine
    prompts = _prompts(3, rs, vocab=mm.cfg.vocab_size)
    out, _ = _run(mq, prompts, kv_dtype="int8")
    assert all(len(t) == 8 for t in out.values())


def test_expert_stack_int4_odd_k_roundtrip():
    rs = np.random.RandomState(19)
    w = jnp.asarray(rs.normal(size=(3, 5, 8)), jnp.float32)  # odd K=5
    qs = expert_stack_quantize(w, "weight_only_int4")
    assert qs.bits == 4 and qs.q.shape == (3, 3, 8)          # packed K
    err = np.abs(np.asarray(qs.dequantize()) - np.asarray(w)).max()
    assert err < float(jnp.abs(w).max()) / 7 + 1e-6          # 4-bit grid


def test_gptq_refuses_moe():
    pt.seed(4)
    mm = MixtralForCausalLM(MixtralConfig.tiny())
    with pytest.raises(NotImplementedError):
        quantize_for_serving(mm, "gptq_int8",
                             calib_ids=jnp.zeros((1, 4), jnp.int32))


# ------------------------------------------------ bytes-model satellites

def test_model_geometry_actual_dtypes():
    g = ModelGeometry(num_layers=2, hidden=32, intermediate=64, vocab=64,
                      heads=4, kv_heads=2, head_dim=64, dtype_bytes=2)
    gq = ModelGeometry(num_layers=2, hidden=32, intermediate=64, vocab=64,
                       heads=4, kv_heads=2, head_dim=64, dtype_bytes=2,
                       kv_dtype_bytes=1, kv_scale_bytes=4,
                       weight_dtype_bytes=1.0)
    assert kv_bytes_per_position(g) == 2 * 2 * 2 * 64 * 2
    # int8: 64 codes + 4 scale bytes per (position, head) vs 128 bf16
    assert kv_bytes_per_position(gq) / kv_bytes_per_position(g) \
        == pytest.approx(68 / 128)
    assert weight_bytes(gq) == weight_bytes(g) / 2


def test_engine_geom_and_gauge_read_actual_dtypes(model):
    qm = quantize_for_serving(copy.deepcopy(model), "weight_only_int8")
    eng = _mk(qm, kv_dtype="int8")
    assert eng._geom.kv_dtype_bytes == 1
    assert eng._geom.kv_scale_bytes == 4
    assert eng._geom.weight_dtype_bytes == 1.0
    bf16 = _mk(model)
    assert bf16._geom.kv_dtype_bytes == 0       # inherit dtype_bytes
    assert eng._kv_block_bytes() == cache_block_bytes(eng.cache)
    assert eng._kv_block_bytes() < bf16._kv_block_bytes()
