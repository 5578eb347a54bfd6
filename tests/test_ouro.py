"""Ouro (a decoder whose whole stack of layers runs ``total_ut_steps`` times
a token) against its plain reference, ``chipbench/reference_ouro.py``.

Tiny, float32, CPU: hidden 64, 4 heads of 16, MLP 128, 3 layers, 4 passes,
vocab 256. The program's model is built from the reference's own seeded
tensors by ``chipbench.builders.ouro``, so both sides hold identical
weights and the builder's fused layout is under test too.

Tolerances. Logits and gates here are O(0.5). Program and reference
compute in float32 and differ in the order of their sums (fused q/k/v and
gate/up matmuls, the paged gather, the online softmax), which reads up to
1e-6 on a logit, a gate or a served row's log-probability (measured: plain
forward 8e-7, whole-prompt and chunked prefill with decode 1e-6, seven
preemptions and replays 1e-6). ``TOL`` = 1e-5 is ten times that. The same
weights in bfloat16 read 2e-2 against the reference, two thousand times
``TOL``: ``test_bfloat16_in_place_of_float32_fails`` holds the comparison
to a hundred times. Gradients are compared at a relative 1e-3 with an
absolute floor of 1e-7 (entries run from 1e-6 to 1e-2).
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_ouro as ref
from chipbench.builders import ouro as builder
from paddle_tpu.models import paged
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM, exit_pass
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.serving.kv import cache_block_bytes

TOL = 1e-5
SEED = 2 ** 31 + 11
CFG = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
       "vocab_size": 256, "max_position_embeddings": 512,
       "rms_norm_eps": 1e-6, "rope_theta": 1e6, "sliding_window": None,
       "tie_word_embeddings": False, "initializer_range": 0.02,
       "total_ut_steps": 4, "early_exit_threshold": 1,
       "torch_dtype": "float32"}
L, U = CFG["num_hidden_layers"], CFG["total_ut_steps"]


@pytest.fixture(scope="module")
def model():
    return builder.build(CFG, SEED, remat=False).eval()


def reference(rows, cfg=CFG, **kw):
    return ref.forward(cfg, rows, ref.make_top(SEED, cfg),
                       lambda i: ref.make_layer(SEED, i, cfg), **kw)


def tokens(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(
        1, CFG["vocab_size"], n, dtype=np.int32)


# ------------------------------------------------------- the plain forward
def test_plain_forward_logits_and_gates_match_the_reference(model):
    rows = [tokens(23, 1), tokens(40, 2)]
    for row, (want, want_gates) in zip(rows, reference(rows, with_gates=True)):
        got, gates = model.forward_with_gates(jnp.asarray(row)[None])
        assert gates.shape == (U, 1, len(row))
        np.testing.assert_allclose(got[0], want, atol=TOL, rtol=0)
        np.testing.assert_allclose(gates[:, 0], want_gates, atol=TOL, rtol=0)


def test_exit_rule_below_threshold_one_matches_the_reference(model):
    """With the threshold under 1 tokens leave at different passes: the
    program's rule and the reference's pick the same pass everywhere, and
    the logits are those of that pass."""
    row = tokens(32, 3)
    cfg = dict(CFG, early_exit_threshold=0.5)
    early = builder.build(cfg, SEED, remat=False).eval()
    (want, gates), = reference([row], cfg, with_gates=True)
    leave = np.asarray(exit_pass(jnp.asarray(gates), 0.5))
    np.testing.assert_array_equal(leave, ref.exit_pass(gates, 0.5))
    assert (leave < U - 1).any()            # the rule does something at 0.5
    np.testing.assert_allclose(early(jnp.asarray(row)[None])[0], want,
                               atol=TOL, rtol=0)
    # at the published threshold no token leaves before the last pass
    assert (np.asarray(exit_pass(jnp.asarray(gates), 1.0)) == U - 1).all()


def test_gradient_of_shared_weights_is_the_sum_over_passes(model):
    """``jax.grad`` of the program's loss against ``jax.vjp`` of the
    reference: a layer's tensors are used in every pass, so its gradient is
    the sum of the four passes' contributions, which is what differentiating
    the reference's loop gives."""
    row = tokens(24, 4)
    ids, labels = row[None, :-1], row[None, 1:]
    grads = jax.grad(lambda m: m.loss(jnp.asarray(ids), jnp.asarray(labels)))(
        model)

    top = {k: v.astype(jnp.float32) for k, v in ref.make_top(SEED, CFG).items()}
    layers = [ref.make_layer(SEED, i, CFG) for i in range(L)]

    def loss(top, layers):
        logits, = ref.forward(CFG, [ids[0]], top, lambda i: layers[i])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[0][:, None], 1))

    value, back = jax.vjp(loss, top, layers)
    d_top, d_layers = back(jnp.float32(1.0))
    np.testing.assert_allclose(
        model.loss(jnp.asarray(ids), jnp.asarray(labels)), value, atol=TOL)
    lyr = grads.model.layers[1]
    want = d_layers[1]
    pairs = [(lyr.self_attn.qkv_proj,
              jnp.concatenate([want["wq"], want["wk"], want["wv"]], 1)),
             (lyr.self_attn.o_proj, want["wo"]),
             (lyr.mlp.gate_up_proj,
              jnp.concatenate([want["w_gate"], want["w_up"]], 1)),
             (lyr.mlp.down_proj, want["w_down"]),
             (lyr.input_layernorm_2.weight, want["ln_attn_out"]),
             (lyr.post_attention_layernorm_2.weight, want["ln_mlp_out"]),
             (grads.model.norm.weight, d_top["norm"]),
             (grads.lm_head, d_top["head"]),
             (grads.model.embed_tokens, d_top["embed"])]
    for got, want in pairs:
        assert float(jnp.abs(want).max()) > 1e-6      # a gradient flows
        np.testing.assert_allclose(got, want, atol=1e-7, rtol=1e-3)


def test_one_pass_without_branch_norms_is_the_llama_model_bit_for_bit():
    """Through the paged programs, whose ``_residual`` adds the bare branch
    where a layer has no norm of it (the plain Ouro layer always has both:
    the published architecture has no other shape)."""
    lcfg = LlamaConfig.tiny()
    llama = LlamaForCausalLM(lcfg).eval()
    ouro = OuroForCausalLM(OuroConfig.tiny(
        total_ut_steps=1, num_hidden_layers=lcfg.num_hidden_layers,
        num_key_value_heads=lcfg.num_key_value_heads)).eval()
    ouro.model.embed_tokens, ouro.lm_head = llama.model.embed_tokens, llama.lm_head
    ouro.model.norm.weight = llama.model.norm.weight
    for a, b in zip(ouro.model.layers, llama.model.layers):
        del a.input_layernorm_2, a.post_attention_layernorm_2
        a.self_attn, a.mlp = b.self_attn, b.mlp
        a.input_layernorm = b.input_layernorm
        a.post_attention_layernorm = b.post_attention_layernorm
    ids = jnp.asarray(tokens(20, 5))[None]

    def prefill_logits(model):
        cache = paged.PagedKVCache.init_for(model.cfg, 8, 4, 1, 8)
        cache.block_tables = jnp.arange(8, dtype=jnp.int32)[None]
        return np.asarray(paged.llama_prefill_paged(model, ids, [20], cache)[0])

    assert np.array_equal(prefill_logits(ouro), prefill_logits(llama))
    out, cache = paged.paged_generate(ouro, ids, [20], max_new_tokens=4,
                                      block_size=4)
    want, _ = paged.paged_generate(llama, ids, [20], max_new_tokens=4,
                                   block_size=4)
    assert cache.passes == 1 and np.array_equal(np.asarray(out),
                                                np.asarray(want))


# ------------------------------------------------------------ served
class Tap:
    """For every token the engine emits, the row of logits (from one of
    the two prefill programs) or of log-probabilities (from the decode
    tick, asked to return them) that chose it, by request."""

    def __init__(self, eng):
        self.rows, pending, exe = {}, {}, eng.exe

        def tapped(fn, slots_at):
            def call(*a, **kw):
                out = fn(*a, **kw)
                for i, s in enumerate(np.asarray(a[slots_at])):
                    if s < eng.num_slots:
                        pending[int(s)] = out[i]
                return out
            return call

        exe.prefill = tapped(exe.prefill, 2)
        exe.prefill_chunk = tapped(exe.prefill_chunk, 3)
        tick, emit = exe.decode_tick, eng._emit

        def decode_tick(last_tok, run_mask, *a, **kw):
            a = list(a)
            a[5] = True                       # need_logp: keep the rows
            nxt, logp = tick(last_tok, run_mask, *a, **kw)
            for s in np.nonzero(run_mask)[0]:
                pending[int(s)] = logp[s]
            return nxt, logp

        def emitted(slot, token):
            rid = int(eng.slot_req[slot])
            self.rows.setdefault(rid, []).append(np.asarray(pending[slot]))
            return emit(slot, token)

        exe.decode_tick, eng._emit = decode_tick, emitted


def served_against_reference(eng, prompts, new_tokens):
    """Serve ``prompts``; -> the largest gap between the log-softmax of the
    row that chose each served token and the reference's at that position
    (its full forward over prompt + served tokens), and the outputs."""
    tap = Tap(eng)
    rids = [eng.add_request(Request(p, max_new_tokens=n))
            for p, n in zip(prompts, new_tokens)]
    out = eng.run()
    worst = 0.0
    for rid, p in zip(rids, prompts):
        toks = np.asarray(out[rid])
        seq = np.concatenate([p, toks[:-1]])
        want, = reference([seq], keep=[np.arange(len(p) - 1, len(seq))])
        got = jax.nn.log_softmax(jnp.stack(tap.rows[rid]), axis=-1)
        assert got.shape == want.shape
        np.testing.assert_array_equal(toks, np.argmax(got, -1))
        worst = max(worst, float(jnp.abs(
            got - jax.nn.log_softmax(want, axis=-1)).max()))
    return worst, out


def engine(model, **kw):
    return LLMEngine(model, **{**dict(num_slots=4, block_size=4,
                                      max_prompt_len=16, max_seq_len=96,
                                      num_blocks=96), **kw})


SERVED = {
    # prompts that fit the prefill window: the whole-prompt program
    "whole_prompt_prefill": (dict(), [9, 16, 5], [8, 6, 10]),
    # prompts over the window stream in through the chunk program
    "chunked_prefill": (dict(), [37, 50], [6, 6]),
    # int8 K/V is a different result: run below, compared loosely
}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_logits_match_the_reference_at_every_position(model, case):
    opts, lens, new = SERVED[case]
    eng = engine(model, **opts)
    worst, _ = served_against_reference(
        eng, [tokens(n, 10 + i) for i, n in enumerate(lens)], new)
    assert worst < TOL, worst
    assert eng.cache.passes == U and eng.cache.cache_layers == L * U


def test_radix_hit_with_copy_on_write_matches_the_reference(model):
    """The second prompt parts from the first in the middle of a block: its
    shared blocks are adopted, the boundary block is copied (every pass's
    row of it) and the rest prefilled through the chunk program."""
    eng = engine(model)
    first = tokens(30, 20)
    worst, _ = served_against_reference(eng, [first], [4])
    second = np.concatenate([first[:22], tokens(9, 21)])
    before = dict(eng.mgr.cache_stats)
    worst2, _ = served_against_reference(eng, [second], [6])
    assert eng.mgr.cache_stats["partial_hits"] == before["partial_hits"] + 1
    assert eng.mgr.cache_stats["token_hits"] - before["token_hits"] == 22
    assert max(worst, worst2) < TOL, (worst, worst2)


def test_a_preempted_and_replayed_request_matches_the_reference(model):
    """A pool too small for both answers: the younger request is evicted,
    re-queued with what it had generated and replayed."""
    eng = engine(model, num_slots=2, num_blocks=14, preemption=True,
                 prefix_caching=False)
    worst, out = served_against_reference(
        eng, [tokens(12, 30), tokens(14, 31)], [24, 24])
    assert eng.stats["preemptions"] >= 1
    assert all(len(t) == 24 for t in out.values())
    assert worst < TOL, worst


def test_bfloat16_in_place_of_float32_fails(model):
    """The tolerance is tight enough to tell the next precision down: the
    same weights rounded to bfloat16, served, miss it a hundredfold."""
    low = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        model)
    low.cfg = low.model.cfg = OuroConfig(**{**vars(model.cfg),
                                            "dtype": jnp.bfloat16})
    eng = engine(low)
    tap = Tap(eng)
    p = tokens(16, 40)
    rid = eng.add_request(Request(p, max_new_tokens=6))
    toks = np.asarray(eng.run()[rid])
    seq = np.concatenate([p, toks[:-1]])
    want, = reference([seq], keep=[np.arange(len(p) - 1, len(seq))])
    got = jax.nn.log_softmax(jnp.stack(tap.rows[rid]).astype(jnp.float32), -1)
    assert float(jnp.abs(got - jax.nn.log_softmax(want, -1)).max()) > 100 * TOL


# ------------------------------------------------------------ the cache
def test_cache_holds_a_layer_for_every_pass(model):
    eng = engine(model)
    one_pass = OuroForCausalLM(OuroConfig(**{**vars(model.cfg),
                                             "total_ut_steps": 1})).eval()
    plain = engine(one_pass)
    assert (eng.cache.cache_layers, plain.cache.cache_layers) == (L * U, L)
    assert eng.cache.num_blocks == plain.cache.num_blocks == 96
    assert eng.cache.pool_rows == U * 96
    assert cache_block_bytes(eng.cache) == U * cache_block_bytes(plain.cache)
    per_token = 2 * 4 * 16 * 4 * L * U          # K and V, heads, dim, f32
    assert eng.stats["cache_bytes_per_token"] == per_token
    assert eng._geom.num_layers == L * U        # the roofline's layer count
    quant = engine(model, kv_dtype="int8")
    assert cache_block_bytes(quant.cache) == 4 * (2 * 4 * (16 + 4)) * L * U


def test_int8_kv_and_weight_only_int8_run_on_it():
    """The control of the benchmark's cell: the program's own int8 paths.
    They change the result (so no tight tolerance) but must run, stay
    finite and stay near: the served tokens' reference logits lie within
    0.05 of the reference's best."""
    from paddle_tpu.serving.quant import quantize_for_serving
    m = quantize_for_serving(builder.build(CFG, SEED, remat=False).eval())
    assert type(m.model.layers[0].self_attn.qkv_proj).__name__ == \
        "QuantizedWeight"
    eng = engine(m, kv_dtype="int8")
    assert len(eng.cache.k_scales) == L
    assert eng.cache.k_scales[0].shape[0] == U * 96
    p = tokens(21, 50)
    rid = eng.add_request(Request(p, max_new_tokens=8))
    toks = np.asarray(eng.run()[rid])
    seq = np.concatenate([p, toks[:-1]])
    want, = reference([seq], keep=[np.arange(len(p) - 1, len(seq))])
    want = np.asarray(want)
    gap = want.max(-1) - want[np.arange(len(toks)), toks]
    assert np.isfinite(gap).all() and gap.max() < 0.05, gap


LLAMA_LAYERS = LlamaConfig.tiny().num_hidden_layers


@pytest.mark.parametrize("looped, want", [
    (True, {"ut_steps": U, "cache_layers": L * U}),
    (False, {"ut_steps": 1, "cache_layers": LLAMA_LAYERS})])
def test_program_spans_say_how_many_passes_and_cache_layers(model, looped,
                                                            want):
    """So a reader of ``kv_blocks`` need not know the family: the decode
    span and the executor's three program spans carry both numbers, 1 and
    the layer count for a one-pass model."""
    from paddle_tpu.observability import TRACER
    eng = engine(model if looped else LlamaForCausalLM(LlamaConfig.tiny()))
    eng.add_request(Request(tokens(9, 60), max_new_tokens=3))
    eng.add_request(Request(tokens(21, 61), max_new_tokens=3))    # chunked
    TRACER.clear()
    TRACER.enable()
    try:
        eng.run()
    finally:
        TRACER.disable()
    events = [e for e in TRACER.export()["traceEvents"] if e["ph"] == "X"]
    TRACER.clear()
    for name in ("serving.decode", "exe.decode_tick", "exe.prefill",
                 "exe.prefill_chunk"):
        args = [e["args"] for e in events if e["name"] == name]
        assert args, name
        assert all({k: a[k] for k in want} == want for a in args), name
    assert all("kv_blocks" in e["args"] for e in events
               if e["name"] == "serving.decode")


# ---------------------------------------------------------- what is refused
def _draft():
    return LlamaForCausalLM(LlamaConfig.tiny())


REFUSED = {
    "cp": (lambda m: engine(m, cp=2), "context parallelism"),
    "multi_lora": (lambda m: engine(m, adapter_store=object()), "multi-LoRA"),
    "draft_model": (lambda m: engine(m, draft_model=_draft()),
                    "a draft model"),
    "early_exit": (lambda m: engine(builder.build(
        dict(CFG, early_exit_threshold=0.5), SEED)), "early_exit_threshold"),
    "beam_request": (lambda m: engine(m).add_request(
        Request(tokens(8), max_new_tokens=4, num_beams=2)), "beam search"),
    "kv_handoff": (lambda m: engine(m).extract_sequence(0), "KV handoff"),
    "paged_beam_search": (lambda m: paged.paged_beam_search(
        m, tokens(8), max_new_tokens=2), "paged_beam_search"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_does_not_compose_is_refused_with_its_message(model, what):
    attempt, message = REFUSED[what]
    with pytest.raises(NotImplementedError, match=message):
        attempt(model)


def test_a_cache_built_for_another_number_of_passes_is_refused(model):
    cache = paged.PagedKVCache.init(L, 8, 4, 4, 16, 1, 4, jnp.float32)
    with pytest.raises(ValueError, match="init_for"):
        paged.llama_decode_step_paged(model, jnp.zeros((1,), jnp.int32),
                                      cache, jnp.ones((1,), bool))


# ------------------------------------------- one-pass programs, as before
def _programs(model, cfg):
    """The jaxpr text of the three paged forwards and the copy-on-write
    update, for a bf16-style and an int8 cache."""
    out = {}
    passes = getattr(cfg, "total_ut_steps", 1)
    looped = {"passes": passes} if passes > 1 else {}
    for kv in (None, "int8"):
        cache = paged.PagedKVCache.init(
            cfg.num_hidden_layers, 8, 4, cfg.num_key_value_heads,
            cfg.hidden_size // cfg.num_attention_heads, 3, 4, cfg.dtype,
            kv_dtype=kv, **looped)
        ids, lens = jnp.zeros((2, 8), jnp.int32), jnp.array([5, 8], jnp.int32)
        slots, rows = jnp.array([0, 1], jnp.int32), jnp.zeros((2, 4), jnp.int32)
        z = jnp.zeros((3,), jnp.int32)
        out[f"prefill.{kv}"] = jax.make_jaxpr(paged.llama_prefill_paged)(
            model, ids, lens, cache, slots, rows)
        out[f"chunk.{kv}"] = jax.make_jaxpr(paged.llama_prefill_chunk_paged)(
            model, ids, lens, jnp.array([4, 0], jnp.int32), cache, slots, rows)
        out[f"tick.{kv}"] = jax.make_jaxpr(
            lambda m, c: paged.llama_decode_tick(
                m, z, c, jnp.ones((3,), bool), z, z, z, jax.random.PRNGKey(0),
                jnp.zeros((3,)), jnp.ones((3,))))(model, cache)
        out[f"cow.{kv}"] = jax.make_jaxpr(paged._prefix_cow_update)(cache, z, z)
    return {k: str(v) for k, v in out.items()}


def _loops(jaxpr_text):
    return len(re.findall(r"= (?:scan|while)\[", jaxpr_text))


# sha256[:16] of the programs a one-pass model traced at the parent of the
# PR that brought looped models (the tiny Mistral below, jax 0.9.0). A PR
# that means to change one of these programs replaces its digest. (PR 29
# rewrote the chunk KERNEL: the CPU trace takes the gather and never sees
# it, so ``chunk.*`` stand as they were, like the other six. PR 38 put
# the sampler's stochastic path under a ``cond``: both ``tick.*`` moved.)
ONE_PASS_DIGESTS = {
    "prefill.None": "406290326ee86fc9", "chunk.None": "9c3bbc292fc90c5a",
    "tick.None": "fb17388c44e1f2e6", "cow.None": "cfd0dfa73db95ea3",
    "prefill.int8": "e4f98f2e5629e2ba", "chunk.int8": "02d726380f37d7bf",
    "tick.int8": "7a7d0bdc88375bf0", "cow.int8": "2ee99e5c66973264",
}


def test_one_pass_models_trace_the_programs_they_traced_before():
    from paddle_tpu.models.mistral import MistralConfig, MistralForCausalLM
    import paddle_tpu as pt
    cfg = MistralConfig.tiny(sliding_window=None)
    pt.seed(0)
    texts = _programs(MistralForCausalLM(cfg).eval(), cfg)
    for name, text in texts.items():
        assert _loops(text) == 0, name
    if jax.__version__ == "0.9.0":
        got = {k: hashlib.sha256(v.encode()).hexdigest()[:16]
               for k, v in texts.items()}
        assert got == ONE_PASS_DIGESTS


def test_a_looped_model_traces_one_body_a_layer_under_one_loop(model):
    texts = _programs(model, model.cfg)
    for name in ("prefill.None", "chunk.None", "tick.None"):
        assert _loops(texts[name]) == 1, name
    # the copy-on-write update copies every pass's row of a block, unlooped
    assert _loops(texts["cow.None"]) == 0
