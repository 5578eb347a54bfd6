"""Kimi-K2 on the normal serving path (ISSUE 41): latent attention over a
paged latent cache against the plain reference
(``chipbench/reference_kimi_k2.py``: logits, not tokens), the expanded
prefill and the absorbed tick against the plain forward, the sigmoid gate
with its selection bias, one chip's share of the experts against the uncut
layer, prefix hits and copy-on-write on latent blocks, what the family is
refused, and what its spans carry.

Sizes: a dense layer and two expert layers, hidden 64, the published
ratios (rope half of nope, a latent twice a head), 16 experts of which 4 a
token and 4 held, float32, so that a tolerance says something about the
arithmetic and not about bfloat16."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_kimi_k2 as ref
from chipbench.builders import kimi_k2 as builder
from paddle_tpu.distributed import moe
from paddle_tpu.distributed.moe import MoELayer
from paddle_tpu.models import paged
from paddle_tpu.models.kimi_k2 import KimiK2Config
from paddle_tpu.observability import TRACER
from paddle_tpu.ops import attention as A
from paddle_tpu.ops.pallas import latent_attention as L
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.types import Request

CFG = json.loads((Path(__file__).parents[1] / "chipbench" / "tests" / "cells"
                  / "configs" / "tiny-kimi-k2.json").read_text())
SEED = 3
BS = 4                      # block size of every engine here


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return builder.build(CFG, SEED).eval()


def reference(seq, routes=False):
    out = ref.forward(CFG, [np.asarray(seq, np.int32)],
                      ref.make_top(SEED, CFG),
                      lambda i: ref.make_layer(SEED, i, CFG), routes=routes)
    if routes:
        return np.asarray(out[0][0]), np.asarray(out[1][0])
    return np.asarray(out[0])


# ------------------------------------------ rope and scale, by hand values
PUBLISHED_YARN = {"type": "yarn", "factor": 32, "beta_fast": 1,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096}


def test_softmax_scale_and_mscale_are_the_published_numbers():
    assert A.yarn_mscale(PUBLISHED_YARN) == pytest.approx(1.34657, abs=1e-5)
    assert KimiK2Config().softmax_scale == pytest.approx(0.130861, abs=1e-6)
    assert KimiK2Config(rope_scaling=None).softmax_scale == \
        pytest.approx(192 ** -0.5)


def test_yarn_frequencies_blend_between_the_correction_dims():
    inv = np.asarray(A.yarn_inv_freq(64, 50000.0, PUBLISHED_YARN))
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    # 64 ln(4096 / 2 pi) / (2 ln 50000) = 19.16: pairs up to 19 keep the
    # plain frequency, pairs from 20 on are interpolated by the factor
    np.testing.assert_allclose(inv[:20], plain[:20], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], plain[20:] / 32, rtol=1e-6)
    assert inv[1] == pytest.approx(0.713111, rel=1e-5)
    assert inv[20] == pytest.approx(3.6140467e-05, rel=1e-5)
    # and the reference's own, written apart from the program's
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(
        {"qk_rope_head_dim": 64, "rope_theta": 50000,
         "rope_scaling": PUBLISHED_YARN}), rtol=1e-6)
    # bounds a factor apart leave a ramp between them
    wide = np.asarray(A.yarn_inv_freq(64, 50000.0, {**PUBLISHED_YARN,
                                                    "beta_fast": 32}))
    between = (wide < plain * 0.999) & (wide > plain / 32 * 1.001)
    assert between.sum() >= 5


# ------------------------------------------------------- the latent kernels
def _pool_case(seed=0):
    rng = np.random.default_rng(seed)
    n, bs, w = 40, 16, 256
    pool = jnp.asarray(rng.normal(size=(n, bs, w)) * 0.5, jnp.bfloat16)
    return rng, n, bs, w, pool, rng.permutation(n)


def _tables(lens, perm, n, bs, width=12):
    tables, k = np.full((len(lens), width), n, np.int32), 0
    for b, length in enumerate(lens):
        nb = -(-int(length) // bs)
        tables[b, :nb] = perm[k:k + nb]
        k += nb
    return tables


def test_latent_decode_kernel_is_its_gather_twin():
    rng, n, bs, w, pool, perm = _pool_case()
    lens = np.array([37, 0, 150], np.int32)
    tables = _tables(lens, perm, n, bs)
    q = jnp.asarray(rng.normal(size=(3, 8, w)), jnp.bfloat16)
    kw = dict(v_width=128, scale=0.1)
    got = L.paged_latent_decode_attention_pallas(
        q, pool, jnp.asarray(tables), jnp.asarray(lens), interpret=True, **kw)
    want = L.paged_latent_decode_attention_xla(
        q, pool, jnp.asarray(tables), jnp.asarray(lens), **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=8e-3)
    assert float(jnp.abs(got[1]).max()) == 0.0      # a row of length 0


# the expanded chunk kernel's cases: (offsets, chunk_lens, chunk, the
# kernel's forced sizes, KB a slot of its row buffer: 16 is a compute block
# of 32 rows); the pool's row is [c_kv 128 | k_r 64 | 0], 4 heads
CHUNK_CASES = {
    "offset_0_full_chunk": ([0], [24], 24, {}, 512),
    "offset_off_block_and_tile": (
        [37], [300], 300, dict(q_tile=256, sub_tile=128), 16),
    "tail_of_3_at_a_long_offset": ([170], [3], 24, {}, 16),
    "unequal_rows_a_dead_one_between": (
        [16, 0, 100], [21, 0, 24], 24, {}, 16),
    "forced_small_q_tile_two_heads_a_step": (
        [5, 130], [140, 9], 140, dict(q_tile=128, heads=2), 40),
}


def _chunk_case(offs, cl, c, seed=1):
    rng, n, bs, w, pool, perm = _pool_case(seed)
    pool = pool.at[:, :, 192:].set(0)
    offs, cl = np.array(offs, np.int32), np.array(cl, np.int32)
    tables = _tables(offs + cl, perm, n, bs, width=24)
    q_nope = jnp.asarray(rng.normal(size=(len(offs), c, 4, 128)),
                         jnp.bfloat16)
    q_rope = jnp.asarray(rng.normal(size=(len(offs), c, 4, 64)),
                         jnp.bfloat16)
    w_kvb = jnp.asarray(rng.normal(size=(128, 4, 256)) * 0.1, jnp.bfloat16)
    return (q_nope, q_rope, w_kvb, pool, tables, offs, cl)


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_latent_chunk_kernel_is_its_gather_twin(case, monkeypatch):
    offs, cl, c, sizes, buffer_kb = CHUNK_CASES[case]
    args = _chunk_case(offs, cl, c)
    monkeypatch.setattr(L, "_BUFFER_BYTES", buffer_kb * 1024)
    paged.clear_jit_caches()          # the kernel's own jit among them
    try:
        got = L.paged_latent_chunk_attention_pallas(
            *args, scale=0.1, interpret=True, **sizes)
    finally:
        paged.clear_jit_caches()
    want = L.paged_latent_chunk_attention_xla(*args, scale=0.1)
    assert got.shape == (len(offs), c, 4, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=8e-3)
    # a dead row and a live row's positions past its length emit zeros
    for row, n in enumerate(cl):
        assert float(jnp.abs(got[row, n:]).max(initial=0.0)) == 0.0
        assert n == 0 or float(jnp.abs(got[row, :n]).max()) > 0.0


def test_the_expanded_kernel_is_the_plain_expanded_forward():
    """``KimiK2Attention.__call__`` over a whole row (K and V of every
    position expanded by XLA, no cache) against the kernel over the row's
    latent cache, chunk by chunk: every live position's branch output."""
    from paddle_tpu.models.kimi_k2 import KimiK2Attention
    rng = np.random.default_rng(8)
    att = KimiK2Attention(KimiK2Config.tiny())
    s, bs, n = 45, 8, 12
    u = jnp.asarray(rng.normal(size=(1, s, 64)), jnp.float32)
    want = np.asarray(att(u, *att.rope(jnp.arange(s)[None])))
    tables = rng.permutation(n)[None, :8].astype(np.int32)
    pool = jnp.zeros((n, bs, att.row_width), jnp.float32)
    for off, ln, c in ((0, 13, 16), (13, 32, 32)):
        pos = off + jnp.arange(c)[None]
        rope = att.rope(pos)
        h = jnp.zeros((1, c, 64)).at[:, :ln].set(u[:, off:off + ln])
        rows = att.cache_rows(h, *rope)[0, :ln]
        at = np.arange(off, off + ln)
        pool = pool.at[tables[0, at // bs], at % bs].set(rows)
        got = att.expanded(
            h, *rope, lambda q_nope, q_rope, w_kvb:
            L.paged_latent_chunk_attention_pallas(
                q_nope, q_rope, w_kvb, pool, tables, [off], [ln],
                scale=att.scale, interpret=True))
        np.testing.assert_allclose(np.asarray(got)[0, :ln],
                                   want[0, off:off + ln], atol=2e-5)


def test_the_slab_rule_names_what_mosaic_copies():
    assert L.latent_row_width(512, 64) == 640
    assert L.latent_slab_is_tiled(16, 640, 512, jnp.bfloat16)
    assert not L.latent_slab_is_tiled(8, 640, 512, jnp.bfloat16)
    assert not L.latent_slab_is_tiled(16, 576, 512, jnp.bfloat16)
    assert L.latent_slab_is_tiled(8, 128, 128, jnp.float32)


# ------------------------------- the forwards against the plain reference
def fresh_cache(model, slots=2, blocks=32):
    return paged.PagedKVCache.init_for(model.cfg, blocks, BS, slots,
                                       blocks // slots)


def test_the_cache_is_one_latent_pool_a_layer(model):
    cache = fresh_cache(model)
    assert paged.layer_kinds(model.cfg) == (paged.LATENT_LAYER,) * 3
    assert cache.v_pools == [] and cache.cache_layers == 3
    assert cache.k_pools[0].shape == (32, BS, 128)      # 32 + 8 -> 128
    assert (cache.block_size, cache.num_blocks) == (BS, 32)


def test_prefill_then_decode_is_the_reference_at_every_step(model):
    rng = np.random.default_rng(5)
    seq = list(rng.integers(1, 256, 19))
    cache = fresh_cache(model)
    ids = np.zeros((2, 24), np.int32)
    ids[0, :19] = seq
    rows = np.full((2, 16), 32, np.int32)
    rows[0, :6] = np.arange(6) + 3
    logits, cache = paged.llama_prefill_paged(
        model, jnp.asarray(ids), jnp.array([19, 0]), cache,
        jnp.array([0, 2]), jnp.asarray(rows))
    for _ in range(8):
        want = reference(seq)
        # float32 both: the prefill expanded from the cache's rows, then
        # each tick absorbed over them, against the expanded form over the
        # whole row
        np.testing.assert_allclose(np.asarray(logits)[0], want[-1], atol=5e-5)
        seq.append(int(np.argmax(want[-1])))
        tables = cache.block_tables.at[0, :8].set(jnp.arange(8) + 3)
        cache = paged.replace(cache, block_tables=tables)
        routed = []
        logits, cache = paged.llama_decode_step_paged(
            model, jnp.array([seq[-1], 0]), cache, jnp.array([True, False]),
            routed=routed)
        # what the step says it routed: the new token's held choices
        _, masks = reference(seq, routes=True)
        assert int(routed[0][0]) == sum(
            bin(int(m)).count("1") for m in masks[:, -1])


def test_expanded_prefill_then_absorbed_ticks_are_the_plain_forward(model):
    """The one place the two forms meet: chunks at an offset through the
    latent cache (EXPANDED over the rows as cached), then decode ticks over
    the same cache (ABSORBED), against the model's plain forward (expanded,
    no cache): every position's logits, then every step's."""
    rng = np.random.default_rng(6)
    seq = list(rng.integers(1, 256, 29, dtype=np.int32))
    want = np.asarray(model(jnp.asarray(seq)[None])[0])
    cache = fresh_cache(model, slots=1)
    rows = np.full((1, 32), 32, np.int32)
    rows[0, :8] = np.arange(8)
    got = []
    for off, n in ((0, 13), (13, 16)):
        ids = np.zeros((1, 16), np.int32)
        ids[0, :n] = seq[off:off + n]
        logits, cache = paged.llama_prefill_chunk_paged(
            model, jnp.asarray(ids), jnp.array([n]), jnp.array([off]), cache,
            jnp.array([0]), jnp.asarray(rows), full_logits=True)
        got.append(np.asarray(logits)[0, :n])
    np.testing.assert_allclose(np.concatenate(got), want, atol=5e-5)
    cache = paged.replace(
        cache, block_tables=cache.block_tables.at[0, 8:10].set(
            jnp.arange(2) + 8))
    for _ in range(3):
        seq.append(int(np.argmax(want[-1])))
        logits, cache = paged.llama_decode_step_paged(
            model, jnp.array([seq[-1]]), cache, jnp.array([True]))
        want = np.asarray(model(jnp.asarray(seq)[None])[0])
        np.testing.assert_allclose(np.asarray(logits)[0], want[-1],
                                   atol=5e-5)


# ------------------------------------------------------ the serving engine
DOC = np.random.default_rng(7).integers(1, 256, 32, dtype=np.int32)


def tail(n, seed):
    return np.concatenate([DOC, np.random.default_rng(seed).integers(
        1, 256, n, dtype=np.int32)])


PROMPT = tail(9, 0)


def traced(fn):
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
                max_seq_len=128, num_blocks=64)
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


def prefix_hit(model):
    """The document's latent blocks are adopted, not computed."""
    eng = engine(model)
    serve(eng, tail(7, 1))
    toks = serve(eng, PROMPT)
    assert eng.mgr.cache_stats["token_hits"] == 32
    return eng, toks


def copy_on_write(model):
    """PROMPT parts from a cached prompt in the middle of a block: the
    boundary block's latent rows are copied, then written on."""
    eng = engine(model)
    other = PROMPT.copy()
    other[38:] = (other[38:] + 1) % 255 + 1         # 38 = 9 blocks + 2
    serve(eng, other)
    toks = serve(eng, PROMPT)
    stats = eng.mgr.cache_stats
    assert stats["partial_hits"] == 1 and stats["token_hits"] == 38
    return eng, toks


PATHS = {"cold-whole-prompt": cold_whole, "chunks-of-8": chunked(8),
         "chunks-of-16": chunked(16), "prefix-hit": prefix_hit,
         "copy-on-write": copy_on_write}


@pytest.fixture(scope="module")
def want():
    seq, first = list(PROMPT), None
    for _ in range(6):
        lg = reference(seq)[-1]
        first = lg if first is None else first
        seq.append(int(np.argmax(lg)))
    return first, seq[len(PROMPT):]


@pytest.mark.parametrize("path", PATHS)
def test_every_path_to_a_first_token_gives_the_reference_logits(
        model, want, path):
    first, tokens = want
    eng, got = PATHS[path](model)
    assert got == tokens
    np.testing.assert_allclose(eng.first_logits[-1][0], first, atol=5e-5)
    eng.assert_quiescent()
    assert eng.kv.reconcile()["ok"]


def test_the_spans_carry_what_the_host_counts(model):
    """One request: every prefill call's and every tick's ``routed_pairs``
    and ``experts_hit`` are the reference's count over the tokens that
    call computed (a token's held choices a layer; in a call of one token
    the experts hit are the same number)."""
    eng = engine(model, max_prompt_len=16)
    toks = []
    events = traced(lambda: toks.extend(serve(eng, PROMPT)))
    _, masks = reference(np.concatenate([PROMPT, toks[:-1]]), routes=True)
    chosen = np.array([[bin(int(m)).count("1") for m in layer]
                       for layer in masks])                # [layers, S]
    hit = lambda lo, hi: sum(
        bin(int(np.bitwise_or.reduce(layer[lo:hi]))).count("1")
        for layer in masks)
    calls = [e["args"] for e in events if e["name"] in
             ("exe.prefill", "exe.prefill_chunk")]
    routed = [e["args"] for e in events if e["name"] == "exe.routed"]
    assert len(calls) == len(routed) == 3                  # 16 + 16 + 9
    at = 0
    for call, r in zip(calls, routed):
        assert call["cache_layers"] == 3 and r["program"] in ("prefill",
                                                              "chunk")
        n = call["useful"]
        assert r["routed_pairs"] == chosen[:, at:at + n].sum()
        assert r["experts_hit"] == hit(at, at + n)
        at += n
        assert call["ctx_tokens"] == at
    ticks = [e["args"] for e in events if e["name"] == "serving.decode"]
    assert len(ticks) == 5
    for t in ticks:
        assert (t["cache_layers"], t["slots"]) == (3, 1)
        assert t["routed_pairs"] == t["experts_hit"] == chosen[:, at].sum()
        at += 1
    # the counts came back with fetches the engine makes anyway
    waits = [e for e in events if e.get("cat") == "device_wait"]
    assert {e["name"] for e in waits} <= {"exe.sample", "serving.fetch"}


# ------------------------------------------------ what the family refuses
def _tiny_llama():
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=256, num_hidden_layers=1, hidden_size=32,
        num_attention_heads=4, num_key_value_heads=4)).eval()


REFUSED_AT_CONSTRUCTION = {
    "cp": (dict(cp=2), "context parallelism"),
    "int8-kv": (dict(kv_dtype="int8"), "quantized K/V pool"),
    "draft-model": (dict(draft_model=_tiny_llama), "a draft model"),
    "multi-lora": (dict(adapter_store=object), "multi-LoRA"),
    "async-depth": (dict(async_depth=2), "async_depth > 0"),
}


@pytest.mark.parametrize("what", REFUSED_AT_CONSTRUCTION)
def test_an_engine_refuses_by_name_what_latent_layers_cannot_do(model, what):
    kw, names = REFUSED_AT_CONSTRUCTION[what]
    kw = {k: v() if callable(v) else v for k, v in kw.items()}
    with pytest.raises(NotImplementedError) as err:
        LLMEngine(model, num_slots=2, block_size=BS, max_prompt_len=16,
                  max_seq_len=64, **kw)
    # cp names the family's experts first: the engine refuses MoE under it
    assert names in str(err.value) or (what == "cp"
                                       and "MoE" in str(err.value))


def test_beams_the_handoff_and_verify_are_refused_by_name(model):
    eng = engine(model)
    with pytest.raises(NotImplementedError, match="latent.*beam search"):
        eng.add_request(Request(PROMPT, max_new_tokens=4, num_beams=2))
    rid = eng.add_request(Request(PROMPT, max_new_tokens=4))
    eng.step()
    with pytest.raises(NotImplementedError, match="latent.*KV handoff"):
        eng.extract_sequence(rid)
    with pytest.raises(NotImplementedError, match="latent.*verify_chunk"):
        eng.exe.verify_chunk(*[np.zeros((1, 1), np.int32)] * 5)
    eng.run()
    with pytest.raises(NotImplementedError, match="group-limited"):
        KimiK2Config.tiny(n_group=8, topk_group=4)


# ----------------------------------------------------------- the router
def _layer(experts=16, k=4, hidden=16, width=8, held=None, bias=None,
           seed=0, **kw):
    import paddle_tpu as pt
    pt.seed(seed)
    layer = MoELayer(hidden, width, experts, k=k, capacity_factor=None,
                     dtype=jnp.float32, router="sigmoid_bias", held=held,
                     **kw)
    kg, ku, kd = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    layer.gate_w = jax.random.normal(kg, (hidden, experts)) * 0.5
    # outputs of order one: a tolerance below is then a relative one
    ex = layer.experts
    ex.gate_up = jax.random.normal(ku, ex.gate_up.shape) * 0.4
    ex.down = jax.random.normal(kd, ex.down.shape) * 0.4
    if bias is not None:
        layer.gate_bias = jnp.asarray(bias, jnp.float32)
    return layer


def _by_hand(layer, x, scale=1.0, renorm=True, held=None):
    """The gate and the experts in numpy, a token and an expert at a time."""
    x = np.asarray(x, np.float64)
    w, b = np.asarray(layer.gate_w, np.float64), np.asarray(layer.gate_bias)
    gu = np.asarray(layer.experts.gate_up, np.float64)
    dn = np.asarray(layer.experts.down, np.float64)
    held = tuple(range(w.shape[1])) if held is None else held
    out = np.zeros_like(x)
    for t, u in enumerate(x):
        s = 1 / (1 + np.exp(-(u @ w)))
        choice = np.argsort(-(s + b), kind="stable")[:layer.k]
        total = s[choice].sum() + 1e-20 if renorm else 1.0
        for e in choice:
            if e not in held:
                continue
            j = held.index(e)
            g, up = np.split(u @ gu[j], 2)
            out[t] += scale * s[e] / total * (
                (g / (1 + np.exp(-g)) * up) @ dn[j])
    return out


X = jax.random.normal(jax.random.PRNGKey(9), (2, 12, 16))
BIAS = 0.3 * np.cos(np.pi * np.arange(16))       # moves the selection

ROUTER_CASES = {
    # selection by the biased score, weights from the unbiased one
    "bias-selects-only": dict(bias=BIAS),
    "no-renormalisation": dict(bias=BIAS, norm_topk_prob=False),
    "scaled-by-2.827": dict(bias=BIAS, routed_scale=2.827),
}


@pytest.mark.parametrize("case", ROUTER_CASES)
def test_the_sigmoid_gate_by_hand(case):
    kw = ROUTER_CASES[case]
    layer = _layer(**kw)
    y, _, m = layer(X, return_metrics=True)
    want = _by_hand(layer, np.asarray(X).reshape(24, 16),
                    scale=kw.get("routed_scale", 1.0),
                    renorm=kw.get("norm_topk_prob", True))
    np.testing.assert_allclose(np.asarray(y).reshape(24, 16), want,
                               atol=2e-5)
    assert np.abs(want).max() > 0.5
    assert int(m["routed_pairs"]) == 24 * 4
    # a program that selected by the unbiased score, or weighted by the
    # biased one, is another layer: the bias moves both
    plain = _layer(**{**kw, "bias": None})
    assert float(jnp.abs(plain(X)[0] - y).max()) > 0.1


def test_a_token_none_of_whose_experts_is_held_gets_no_routed_part():
    # the bias keeps every choice among experts 8..15; 0..3 are held
    bias = np.where(np.arange(16) >= 8, 5.0, 0.0)
    layer = _layer(held=(0, 1, 2, 3), bias=bias)
    y, _, m = layer(X, return_metrics=True)
    assert float(jnp.abs(y).max()) == 0.0
    assert (int(m["routed_pairs"]), int(m["experts_hit"])) == (0, 0)


def test_no_token_is_dropped_when_one_expert_takes_them_all():
    """1,024 tokens, 4 of 64 experts held: a pass gathers 1,024 pairs; with
    every token on held expert 0 (and some on 1..3 besides) the call takes
    a second pass, and a third if it needs one."""
    bias = np.zeros(64)
    bias[0] = 5.0
    layer = _layer(experts=64, held=(0, 1, 2, 3), bias=bias, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 1024, 16))
    assert moe.held_rows(4096, 4, 64) == 1024
    y, _, m = layer(x, return_metrics=True)
    assert int(m["routed_pairs"]) > 1024 and int(m["experts_hit"]) == 4
    want = _by_hand(layer, np.asarray(x)[0], held=(0, 1, 2, 3))
    np.testing.assert_allclose(np.asarray(y)[0], want, atol=5e-5)
    # padding tokens are routed nowhere and counted nowhere
    live = jnp.arange(1024)[None, :] < 100
    y2, _, m2 = layer(x, return_metrics=True, live=live)
    np.testing.assert_allclose(np.asarray(y2)[0, :100], want[:100],
                               atol=5e-5)
    assert float(jnp.abs(y2[0, 100:]).max()) == 0.0
    assert int(m2["routed_pairs"]) < int(m["routed_pairs"]) * 0.15


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares of 4: the routed parts of all shares, plus
    the shared expert counted once, are the uncut layer as the reference
    computes it."""
    cfg = {**CFG, "held_experts": list(range(16)), "n_routed_experts": 16}
    w = ref.make_layer(SEED, 1, cfg)
    u = jax.random.normal(jax.random.PRNGKey(11), (40, 64))
    bias = jnp.asarray(ref.score_bias(cfg))
    f32 = {n: v.astype(jnp.float32) for n, v in w.items()}
    choice, g = ref.route(u, f32["w_router"], bias, 4, 2.827)
    routed, _ = ref.routed_sum(u, choice, g, tuple(range(16)), f32, 40)
    shared = ref._swiglu(u, f32["shared_gate"], f32["shared_up"],
                         f32["shared_down"])
    total, pairs = 0.0, 0
    for share in range(4):
        ids = tuple(range(4 * share, 4 * share + 4))
        layer = MoELayer(64, 32, 16, k=4, capacity_factor=None,
                         dtype=jnp.float32, router="sigmoid_bias",
                         routed_scale=2.827, held=ids)
        layer.gate_w, layer.gate_bias = w["w_router"], bias
        layer.experts.gate_up = jnp.concatenate(
            [w["experts_gate"], w["experts_up"]], -1)[4 * share:4 * share + 4]
        layer.experts.down = w["experts_down"][4 * share:4 * share + 4]
        y, _, m = layer(u[None], return_metrics=True)
        total = total + y[0]
        pairs += int(m["routed_pairs"])
    assert pairs == 40 * 4          # every pair lands on exactly one share
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(routed + shared), atol=2e-6)


# ------------------------------------ the families before, to the bit
@pytest.mark.parametrize("renorm", [True, False])
def test_the_softmax_families_compute_what_they_computed(renorm):
    import paddle_tpu as pt
    pt.seed(1)
    layer = MoELayer(32, 48, 8, k=2, capacity_factor=None,
                     dtype=jnp.float32, norm_topk_prob=renorm)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 32))
    y, aux = layer(x)
    # the forward as it stood before router kinds and held experts
    xt = x.reshape(18, 32)
    route, aux0, _ = moe.top_k_route(xt.astype(jnp.float32) @ layer.gate_w,
                                     2, 18, renorm)
    y0 = moe.grouped_forward(xt, route, layer.experts.gate_up,
                             layer.experts.down, 18)
    assert np.array_equal(np.asarray(y), np.asarray(y0.reshape(2, 9, 32)))
    assert float(aux) == float(aux0)
    assert not hasattr(layer, "gate_bias") and layer.held is None
