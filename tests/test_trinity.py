"""Trinity on the normal serving path (ISSUE 44): window and full
attention as two kinds of layer, each in a block space of its own, against
the plain reference (``chipbench/reference_trinity.py``: logits, not
tokens): whole-prompt prefill, chunked prefill at offsets and decode through
the two spaces, below, at and well past the window, chunk edges on and off
block edges and the window's edge inside a chunk; the same after a freed
window block went to another row; the router; the three controls of the
mechanism; what a model with window layers is refused; what the one-kind
windowed model keeps.

Sizes: a dense layer and then a period of three window layers to one full,
hidden 64, a head of 16 (not hidden / heads), a window of 32, block 8,
16 experts of which 4 a token, float32, so that a tolerance says something
about the arithmetic and not about bfloat16."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_trinity as ref
from chipbench.builders import trinity as builder
from paddle_tpu.models import paged
from paddle_tpu.models.trinity import TrinityConfig, TrinityForCausalLM
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.types import Request

CFG = json.loads((Path(__file__).parents[1] / "chipbench" / "tests" / "cells"
                  / "configs" / "tiny-trinity.json").read_text())
SEED = 3
BS, WINDOW = 8, CFG["sliding_window"]
TOL = dict(atol=2e-5, rtol=1e-4)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return builder.build(CFG, SEED).eval()


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG["vocab_size"], n) \
        .astype(np.int32)


def reference(seq, static=ref.layer_static, cfg=CFG):
    """The reference's logits at every position of ``seq``; the row is
    padded on the right (causal: unseen) to one length, so that the
    reference compiles once."""
    row = np.resize(np.asarray(seq, np.int32), 224)
    return np.asarray(ref.forward(
        cfg, [row], ref.make_top(SEED, cfg),
        lambda i: ref.make_layer(SEED, i, cfg), static=static)[0])[:len(seq)]


# ------------------------------------------------- the forwards, by hand
class Row:
    """One sequence fed to the three paged forwards through a cache with
    two block spaces, its tables scattered over both spaces."""

    def __init__(self, model, max_blocks=24, full=(64, 5), window=(40, 3)):
        self.model, self.mb = model, max_blocks
        self.cache = paged.PagedKVCache.init_for(
            model.cfg, full[0], BS, 2, max_blocks, window_blocks=window[0])
        self.full = np.full((1, max_blocks), full[0], np.int32)
        self.full[0, :] = (np.arange(max_blocks) * 7 + full[1]) % full[0]
        self.win = np.full((1, max_blocks), window[0], np.int32)
        self.win[0, :] = (np.arange(max_blocks) * 3 + window[1]) % window[0]
        self.n = 0

    def _rows(self):
        return jnp.asarray(self.full), jnp.asarray(self.win)

    def prefill(self, ids, width):
        pad = np.zeros((1, width), np.int32)
        pad[0, :len(ids)] = ids
        rows, wrows = self._rows()
        logits, self.cache = paged.llama_prefill_paged(
            self.model, jnp.asarray(pad), jnp.asarray([len(ids)]),
            self.cache, jnp.asarray([1]), rows, window_rows=wrows)
        self.n = len(ids)
        return np.asarray(logits)[0]

    def chunk(self, ids, width):
        pad = np.zeros((1, width), np.int32)
        pad[0, :len(ids)] = ids
        rows, wrows = self._rows()
        logits, self.cache = paged.llama_prefill_chunk_paged(
            self.model, jnp.asarray(pad), jnp.asarray([len(ids)]),
            jnp.asarray([self.n]), self.cache, jnp.asarray([1]), rows,
            window_rows=wrows)
        self.n += len(ids)
        return np.asarray(logits)[0]

    def decode(self, tok):
        logits, self.cache = paged.llama_decode_step_paged(
            self.model, jnp.asarray([0, tok], jnp.int32), self.cache,
            jnp.asarray([False, True]))
        self.n += 1
        return np.asarray(logits)[1]

    def forget_below_the_window(self):
        """What the engine's recycling does to the window space's table:
        the entries below the window name another row's block by now."""
        dead = max(0, self.n - WINDOW) // BS
        self.win[0, :dead] = 0
        self.cache.window_tables = self.cache.window_tables.at[
            1, :dead].set(0)


# (whole-prompt tokens, chunk sizes after it, decoded tokens): contexts
# below, at and well past the window of 32; chunk edges on block edges (8,
# 16) and off them (5, 13); the window's edge inside a chunk (a chunk from
# 24 to 37 passes position 32)
CASES = {
    "below_the_window": (20, [], 6),
    "prefill_to_the_window_exactly": (32, [], 4),
    "chunks_on_block_edges": (16, [8, 16, 16], 5),
    "chunks_off_block_edges": (11, [5, 13, 13, 7], 5),
    "the_window_edge_inside_a_chunk": (24, [13, 11], 3),
    "well_past_the_window": (16, [16] * 6, 12),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_chunks_and_decode_agree_with_the_reference(model, case):
    first, chunks, decoded = CASES[case]
    seq = tokens(first + sum(chunks) + decoded, seed=len(case))
    want = reference(seq)
    row = Row(model)
    np.testing.assert_allclose(row.prefill(seq[:first], 32),
                               want[first - 1], **TOL)
    for c in chunks:
        got = row.chunk(seq[row.n:row.n + c], 16)
        np.testing.assert_allclose(got, want[row.n - 1], **TOL)
        row.forget_below_the_window()
    for _ in range(decoded):
        got = row.decode(int(seq[row.n]))
        np.testing.assert_allclose(got, want[row.n - 1], **TOL)
        row.forget_below_the_window()


def test_the_two_spaces_are_two_pool_sizes_and_two_tables(model):
    cache = Row(model).cache
    assert cache.window_layers == (0, 1, 3, 4)
    assert cache.num_blocks == 64 and cache.window_blocks == 40
    assert [p.shape[0] for p in cache.k_pools] == [40, 40, 64, 40, 40]
    assert cache.window_tables.shape == cache.block_tables.shape
    assert int(cache.window_tables[0, 0]) == 40      # its own sentinel
    assert paged.kv_windows(model.cfg) == (32, 32, None, 32, 32)
    # a model of one kind builds what it built: one space, no second table
    from paddle_tpu.models.mistral import MistralConfig
    for window in (None, 6):
        cfg = MistralConfig.tiny(sliding_window=window)
        one = paged.PagedKVCache.init_for(cfg, 16, 4, 2, 8)
        assert one.window_tables is None and one.window_layers == ()
        assert paged.window_space_layers(cfg) == ()
        assert set(paged.kv_windows(cfg)) == {window}


# --------------------------------------------------------------- the engine
def engine(model, **kw):
    opts = dict(num_slots=4, block_size=BS, max_prompt_len=24,
                max_seq_len=200, prefix_caching=False)
    return LLMEngine(model, **{**opts, **kw})


def served_against_the_reference(model, eng, prompts, new=12):
    rids = [eng.add_request(Request(p, max_new_tokens=new)) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        seq = np.concatenate([p, np.asarray(out[rid], np.int32)])
        want = reference(seq)[len(p) - 1:-1]
        assert want.argmax(-1).tolist() == list(out[rid]), len(p)


def test_the_engine_serves_it_through_both_spaces(model):
    eng = engine(model)
    # a window row holds what a window layer reads plus a chunk, a slot
    assert eng.mixed and eng.window == WINDOW
    assert eng.kv.window.num_blocks == 4 * ((WINDOW + 24) // BS + 2)
    prompts = [tokens(n, seed=n) for n in (5, 24, 25, 33, 70, 130, 64, 9)]
    served_against_the_reference(model, eng, prompts)
    eng.assert_quiescent()
    assert eng.kv.window.free_blocks == eng.kv.window.num_blocks


def test_a_recycled_window_block_is_reused_by_another_row(model):
    """A window space so small that the rows can only be served out of
    blocks another row has freed below its window: every block of the
    space is handed out several times over, and the answers stand."""
    eng = engine(model, num_slots=2, num_window_blocks=2 * 9)
    taken = []
    pop = eng.kv.window._pop_free
    eng.kv.window._pop_free = lambda: taken.append(pop()) or taken[-1]
    prompts = [tokens(n, seed=n) for n in (120, 90, 150, 40)]
    served_against_the_reference(model, eng, prompts, new=20)
    assert len(taken) > 3 * eng.kv.window.num_blocks
    assert max(np.bincount(taken)) >= 3
    eng.assert_quiescent()


def test_admission_waits_for_the_window_space(model):
    """Two rows' promises fill a window space of 18 blocks: the third
    request waits for a slot's promise to return, and nothing fails."""
    eng = engine(model, num_window_blocks=18)
    prompts = [tokens(n, seed=n) for n in (100, 110, 120)]
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=4))
    eng.step()
    assert len(eng.queue) == 1 and eng.kv.window_promised == 18
    out = eng.run()
    assert all(len(t) == 4 for t in out.values())
    eng.assert_quiescent()


def test_a_request_that_fits_no_space_is_finished_as_too_long(model):
    eng = engine(model, num_blocks=8)
    rid = eng.add_request(Request(tokens(100), max_new_tokens=4))
    assert eng.requests[rid].finish_reason == "too_long"


# ------------------------------------------------------------ the router
def test_the_router_selects_by_the_biased_score_and_weighs_by_the_unbiased():
    from paddle_tpu.distributed.moe import sigmoid_bias_gate
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(0.4 * np.where(np.arange(16) % 2 == 0, 1, -1),
                       jnp.float32)
    vals, idx = sigmoid_bias_gate(logits, bias, 4, True, 2.826)
    choice, g = ref.route(logits, jnp.eye(16), bias, 4, 2.826)
    assert np.array_equal(np.sort(np.asarray(idx), 1),
                          np.sort(np.asarray(choice), 1))
    order = np.argsort(np.asarray(idx), 1)
    want = np.take_along_axis(
        np.asarray(g), np.argsort(np.asarray(choice), 1), 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(vals), order, 1), want, rtol=1e-6)
    # the bias flips choices, and the weights are the unbiased scores'
    _, plain = sigmoid_bias_gate(logits, bias * 0, 4, True, 2.826)
    flipped = [set(a) != set(b) for a, b in zip(np.asarray(idx).tolist(),
                                                np.asarray(plain).tolist())]
    assert np.mean(flipped) > 0.5
    s = jax.nn.sigmoid(logits)
    picked = np.take_along_axis(np.asarray(s), np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(vals), 2.826 * picked / picked.sum(1, keepdims=True),
        rtol=1e-6)


def test_the_plain_forward_is_the_reference(model):
    seq = tokens(80, seed=5)
    np.testing.assert_allclose(np.asarray(model(jnp.asarray(seq[None])))[0],
                               reference(seq), **TOL)


# --------------------------------- the controls of the mechanism must fail
def _planted(change):
    def static(cfg, i):
        out = ref.layer_static(cfg, i)
        kind = cfg["layer_types"][i]
        for (on, key), value in change.items():
            if on == kind:
                out[key] = value(out[key]) if callable(value) else value
        return out
    return static


CONTROLS = {
    "rope_on_the_global_layer": {(ref.FULL, "rope"): True},
    "no_rope_on_a_window_layer": {(ref.WINDOW, "rope"): False},
    "window_one_too_wide": {(ref.WINDOW, "window"): lambda w: w + 1},
    "window_one_too_narrow": {(ref.WINDOW, "window"): lambda w: w - 1},
}


@pytest.mark.parametrize("what", sorted(CONTROLS))
def test_a_planted_fault_of_the_mechanism_fails_the_comparison(model, what):
    seq = tokens(90, seed=2)
    row = Row(model)
    row.prefill(seq[:24], 32)
    got = [row.chunk(seq[row.n:row.n + 16], 16) for _ in range(4)]
    at = [24 + 16 * (k + 1) - 1 for k in range(4)]
    sound, faulty = reference(seq), reference(seq, _planted(CONTROLS[what]))
    for k, pos in enumerate(at):
        np.testing.assert_allclose(got[k], sound[pos], **TOL)
    gap = max(np.abs(got[k] - faulty[pos]).max() for k, pos in enumerate(at))
    assert gap > 100 * TOL["atol"], gap


# --------------------------------------- refused, each by what it would take
def _beams(model):
    engine(model).add_request(Request(tokens(8), max_new_tokens=4,
                                      num_beams=2))


def _handoff(model):
    eng = engine(model)
    rid = eng.add_request(Request(tokens(8), max_new_tokens=4))
    eng.step()
    eng.extract_sequence(rid)


def _verify(model):
    z = np.zeros((1, 4), np.int32)
    engine(model).exe.verify_chunk(z, [4], [0], [0], np.zeros((1, 25)))


REFUSED = {
    "prefix_caching": (lambda m: LLMEngine(m, block_size=BS),
                       "prefix caching"),
    "beams": (_beams, "beam search"),
    "draft_model": (lambda m: engine(m, draft_model=m), "a draft model"),
    "verify_chunk": (_verify, "verify_chunk"),
    "cp": (lambda m: engine(m, cp=2), "context parallelism"),
    "handoff": (_handoff, "KV handoff"),
    "multi_lora": (lambda m: engine(m, adapter_store=object()),
                   "multi-LoRA"),
    "async_depth": (lambda m: engine(m, async_depth=2), "async_depth"),
    "int8_kv": (lambda m: engine(m, kv_dtype="int8"), "quantized K/V"),
    "preemption": (lambda m: engine(m, preemption=True), "preemption=True"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_is_not_built_over_two_spaces_is_refused(model, what):
    attempt, message = REFUSED[what]
    with pytest.raises(NotImplementedError, match=message) as err:
        attempt(model)
    assert "window (sliding_attention) layers beside full ones" in str(
        err.value)


def test_a_one_kind_windowed_model_is_served_as_it_was():
    """``cfg.sliding_window`` on a model whose every layer is windowed
    (Mistral v0.1's shape): one space, recycled; prefix caching quietly
    off; a prompt longer than a chunk refused as before."""
    import paddle_tpu as pt
    from paddle_tpu.models.mistral import MistralConfig, MistralForCausalLM
    pt.seed(0)
    cfg = MistralConfig.tiny(sliding_window=6, vocab_size=64)
    eng = LLMEngine(MistralForCausalLM(cfg).eval(), num_slots=2,
                    block_size=4, max_prompt_len=16, max_seq_len=64)
    assert eng.window == 6 and not eng.mixed and not eng.prefix_caching
    assert eng.kv.window is None and eng._wspace is eng.mgr
    with pytest.raises(NotImplementedError, match="chunked prefill"):
        eng.add_request(Request(tokens(20) % 64, max_new_tokens=2))
    rid = eng.add_request(Request(tokens(10) % 64, max_new_tokens=30))
    eng.run()
    assert len(eng.requests[rid].tokens) == 30
    eng.assert_quiescent()


def test_the_published_config_is_the_rows():
    cfg = TrinityConfig()
    assert cfg.layer_types.count(paged.FULL_LAYER) == 8
    assert all(cfg.layer_types[i] == paged.FULL_LAYER
               for i in range(3, 32, 4))
    assert paged.head_dim(cfg) == 128 != cfg.hidden_size \
        // cfg.num_attention_heads
    assert len(paged.window_space_layers(cfg)) == 24
    m = jax.eval_shape(lambda: TrinityForCausalLM(TrinityConfig.tiny()))
    assert m.embed_scale == 8.0 and m.layers[2].self_attn.use_rope is False
