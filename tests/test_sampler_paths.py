"""The sampler's two branches (ISSUE 38).

``decoding._sample_rows`` is one ``lax.cond`` on "does any row that runs
this call have a temperature above 0": where none does the call is
``argmax(logits + bias)`` and nothing else; the scale, the full-vocabulary
sort, softmax, cumulative sum and the draw run only when a row samples.
What these tests hold: a greedy row's token is the argmax before and after,
a mixed batch's tokens are those the function of before drew for the same
key, the predicate sees the rows that RUN (a freed slot keeps its last
temperature on the host), the host's counter names the branch the device
took, and the host's key sequence does not depend on the branch.

All CPU, tiny model, none timing-sensitive.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import paged
from paddle_tpu.models.decoding import _sample_rows, generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import METRICS, TRACER
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.serving.executor import _SAMPLE_ROWS_JIT

VOCAB = 64
ENG = dict(num_slots=4, block_size=4, max_prompt_len=16, max_seq_len=64,
           eos_token_id=None)
COSTLY = {"sort", "cumsum", "random_bits", "random_wrap", "exp", "div"}


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=VOCAB, dtype=jnp.float32)
    return LlamaForCausalLM(cfg)


def _before(logits, rng, temps, top_ps, top_k=None, bias=None):
    """``_sample_rows`` as it stood at the parent of ISSUE 38, line for
    line: the reference a mixed batch's tokens are held to."""
    if bias is not None:
        logits = logits + bias
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
    scaled = logits / safe_t
    if top_k is not None and top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[..., -top_k][..., None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_ps[:, None], axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
    scaled = jnp.where(scaled < cutoff, -1e30, scaled)
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temps > 0, sampled, jnp.argmax(logits, axis=-1))


def _logits(rows=8, vocab=1000, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(rows, vocab),
                       jnp.float32)


def _bias(rows, vocab, seed=1):
    """A grammar mask's addend: two words in three forbidden a row."""
    legal = np.random.RandomState(seed).rand(rows, vocab) < 1 / 3
    return jnp.asarray(np.where(legal, 0.0, -1e30), jnp.float32)


def _primitives(jaxpr):
    """The names of every primitive under ``jaxpr``, inner jaxprs too."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


# ------------------------------------------------ (a) every row greedy
@pytest.mark.parametrize("top_k", [None, 5])
@pytest.mark.parametrize("biased", [False, True])
def test_greedy_rows_take_the_argmax_and_nothing_else(biased, top_k):
    logits = _logits()
    bias = _bias(*logits.shape) if biased else None
    temps, top_ps = jnp.zeros(8), jnp.full(8, 0.9)
    got = _sample_rows(logits, jax.random.PRNGKey(3), temps, top_ps, top_k,
                       bias)
    want = jnp.argmax(logits if bias is None else logits + bias, axis=-1)
    np.testing.assert_array_equal(got, want)
    if biased:
        assert np.all(np.asarray(bias)[np.arange(8), np.asarray(got)] == 0)
    np.testing.assert_array_equal(
        got, _before(logits, jax.random.PRNGKey(3), temps, top_ps, top_k,
                     bias))
    # the program: one cond; its greedy branch is an argmax
    jaxpr = jax.make_jaxpr(
        lambda lg, key, t, p, b: _sample_rows(lg, key, t, p, top_k, b))(
            logits, jax.random.PRNGKey(3), temps, top_ps, bias).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    outside = {e.primitive.name for e in jaxpr.eqns} - {"cond"}
    assert not outside & COSTLY, outside
    greedy, stochastic = (_primitives(b.jaxpr)
                          for b in conds[0].params["branches"])
    assert "argmax" in greedy and not greedy & COSTLY, greedy
    assert {"sort", "cumsum", "random_bits", "argmax"} <= stochastic


# ------------------------------------------- (b) mixed rows, one key
MIXED = [0, .7, 0, 1, 0, 0, .3, 0]


@pytest.mark.parametrize("top_p", [1.0, 0.9])
@pytest.mark.parametrize("top_k", [None, 5])
@pytest.mark.parametrize("biased", [False, True])
def test_mixed_rows_draw_the_tokens_of_before(biased, top_k, top_p):
    logits = _logits(seed=2)
    bias = _bias(*logits.shape) if biased else None
    temps = jnp.asarray(MIXED, jnp.float32)
    top_ps = jnp.full(8, top_p, jnp.float32)
    sampling = np.asarray(MIXED) > 0
    drew_off_the_argmax = False
    for seed in range(4):
        key = jax.random.PRNGKey(seed)
        got = np.asarray(_sample_rows(logits, key, temps, top_ps, top_k,
                                      bias))
        np.testing.assert_array_equal(
            got, _before(logits, key, temps, top_ps, top_k, bias))
        best = np.asarray(jnp.argmax(
            logits if bias is None else logits + bias, axis=-1))
        np.testing.assert_array_equal(got[~sampling], best[~sampling])
        drew_off_the_argmax |= bool((got[sampling] != best[sampling]).any())
    assert drew_off_the_argmax             # the draw is a draw


def test_one_sampling_row_is_enough_for_the_stochastic_branch():
    """The predicate is ``any``: the last row alone sampling draws its
    token as before, the seven greedy rows beside it keep the argmax."""
    logits = _logits(seed=4)
    temps = jnp.zeros(8).at[7].set(1.3)
    key = jax.random.PRNGKey(11)
    got = _sample_rows(logits, key, temps, jnp.ones(8))
    np.testing.assert_array_equal(got, _before(logits, key, temps,
                                               jnp.ones(8)))
    np.testing.assert_array_equal(got[:7], jnp.argmax(logits, -1)[:7])


# -------------------- (c) the predicate sees the rows that run the tick
@pytest.fixture
def draws(monkeypatch):
    """Count the calls of ``jax.random.categorical`` that RUN: a host
    callback inside the stochastic branch, so a tick that takes the greedy
    branch leaves the count where it was. Programs traced under the patch
    are dropped on both sides."""
    ran = []
    real = jax.random.categorical

    def counted(key, logits, *a, **kw):
        jax.debug.callback(lambda: ran.append(1))
        return real(key, logits, *a, **kw)

    def clear():
        paged.clear_jit_caches()
        _SAMPLE_ROWS_JIT.clear_cache()

    def count():
        jax.effects_barrier()
        return len(ran)

    monkeypatch.setattr(jax.random, "categorical", counted)
    clear()
    yield count
    clear()


def _tick_inputs(model):
    cfg = model.cfg
    cache = paged.PagedKVCache.init(
        cfg.num_hidden_layers, 8, 4, cfg.num_key_value_heads,
        cfg.hidden_size // cfg.num_attention_heads, 3, 4, cfg.dtype)
    cache = replace(cache, block_tables=jnp.asarray(
        [[0, 1, 8, 8], [2, 3, 8, 8], [4, 5, 8, 8]], jnp.int32))
    tokens = jnp.asarray([5, 9, 17], jnp.int32)
    return cache, tokens


def _sync_tick(model, cache, tokens, runs, temps):
    none = jnp.full(1, 3, jnp.int32)           # a sentinel row: dropped
    z = jnp.zeros(1, jnp.int32)
    nxt, _, _ = paged.llama_decode_tick(
        model, tokens, cache, jnp.asarray(runs), none, z, z,
        jax.random.PRNGKey(2), jnp.asarray(temps, jnp.float32), jnp.ones(3))
    return np.asarray(nxt)


def _async_tick(model, cache, tokens, runs, temps):
    nxt, ran, _, _, _ = paged.llama_decode_tick_async(
        model, tokens, cache, jnp.ones(3, bool), ~jnp.asarray(runs),
        jnp.zeros(3, jnp.int32), jnp.full(3, 9, jnp.int32),
        jax.random.PRNGKey(2), jnp.asarray(temps, jnp.float32), jnp.ones(3),
        jnp.int32(-1))
    np.testing.assert_array_equal(ran, runs)
    return np.asarray(nxt)


@pytest.mark.parametrize("tick", [_sync_tick, _async_tick],
                         ids=["sync", "async"])
def test_a_freed_slots_stale_temperature_keeps_the_tick_greedy(model, draws,
                                                               tick):
    """Slot 1 was freed with its request's temperature of 0.8 still in the
    host's array: it does not run (``active`` false; ``ran`` false in the
    pipelined tick), so the tick takes the argmax alone and the running
    rows' tokens are those of a tick that never heard of 0.8."""
    cache, tokens = _tick_inputs(model)
    runs = np.array([True, False, True])
    clean = tick(model, cache, tokens, runs, [0, 0, 0])
    assert draws() == 0
    stale = tick(model, cache, tokens, runs, [0, 0.8, 0])
    assert draws() == 0                          # the greedy branch still
    np.testing.assert_array_equal(stale, clean)
    assert stale[1] == tokens[1]                 # an idle row keeps its token
    # the probe does count: the same row RUNNING at 0.8 draws
    everyone = np.array([True, True, True])
    drawn = tick(model, cache, tokens, everyone, [0, 0.8, 0])
    assert draws() == 1
    np.testing.assert_array_equal(drawn[[0, 2]], clean[[0, 2]])


# --------------------------------------------- (d) through the engine
def _prompts():
    rs = np.random.RandomState(38)
    return [rs.randint(1, 64, size=n).astype(np.int32)
            for n in (5, 13, 9, 3, 11, 7)]


# what the parent of ISSUE 38 streamed for ``_prompts()`` from this model
# with ``seed=7`` (jax 0.9.0, CPU), request i asked for 6 + i tokens: every
# request greedy, and requests 1 and 4 sampling at 0.8 / top_p 0.9
GREEDY_BEFORE = [
    [27, 34, 4, 34, 4, 34], [27, 34, 4, 34, 4, 34, 4],
    [4, 34, 4, 34, 4, 34, 4, 34], [37, 58, 12, 37, 58, 40, 27, 34, 4],
    [0, 3, 47, 22, 37, 43, 4, 34, 4, 34],
    [44, 24, 56, 18, 38, 27, 34, 4, 34, 4, 34]]
MIXED_BEFORE = [
    GREEDY_BEFORE[0], [13, 53, 42, 44, 29, 59, 34], GREEDY_BEFORE[2],
    GREEDY_BEFORE[3], [52, 26, 11, 22, 15, 9, 38, 45, 11, 29],
    GREEDY_BEFORE[5]]


def _calls():
    c = METRICS.get("serving_sampler_calls_total")
    return {p: c.value(path=p) for p in ("greedy", "stochastic")}


def _delta(before):
    return {p: int(v - before[p]) for p, v in _calls().items()}


def _run(model, sampling=(), **opts):
    """Serve ``_prompts()``, the requests in ``sampling`` at 0.8 / 0.9 ->
    (the engine, each request's streamed tokens)."""
    eng = LLMEngine(model, seed=7, **ENG, **opts)
    streamed = {}
    rids = [eng.add_request(Request(
        p, max_new_tokens=6 + i,
        stream=lambda rq, t: streamed.setdefault(rq.req_id, []).append(
            int(t)),
        **({"temperature": 0.8, "top_p": 0.9} if i in sampling else {})))
        for i, p in enumerate(_prompts())]
    eng.run()
    eng.assert_quiescent()
    assert streamed == {r: list(eng.requests[r].tokens) for r in rids}
    return eng, [streamed[r] for r in rids]


@pytest.mark.parametrize("depth", [0, 2], ids=["sync", "async2"])
def test_a_greedy_run_streams_the_tokens_of_before(model, depth):
    before = _calls()
    eng, got = _run(model, async_depth=depth)
    for p, toks in zip(_prompts(), got):         # and the plain loop's
        solo = np.asarray(generate(model, jnp.asarray(p[None]),
                                   max_new_tokens=len(toks)))[0, len(p):]
        assert toks == solo.tolist()
    if jax.__version__ == "0.9.0":
        assert got == GREEDY_BEFORE
    assert _delta(before)["stochastic"] == 0


def test_a_mixed_run_streams_the_tokens_of_before(model):
    """Same keys, same tokens: the sampling requests' draws and the greedy
    requests beside them in the batch."""
    eng, got = _run(model, sampling=(1, 4))
    for i in (0, 2, 3, 5):
        assert got[i] == GREEDY_BEFORE[i]
    if jax.__version__ == "0.9.0":
        assert got == MIXED_BEFORE


def _sample_calls(eng):
    """Wrap the executor's ``sample_rows`` -> the list its calls land in."""
    seen, real = [], eng.exe.sample_rows

    def counted(*a, **kw):
        seen.append(1)
        return real(*a, **kw)
    eng.exe.sample_rows = counted
    return seen


@pytest.mark.parametrize("depth", [0, 2], ids=["sync", "async2"])
def test_sampler_calls_sum_to_ticks_and_prefill_sample_calls(model, depth):
    eng = LLMEngine(model, seed=7, **ENG, async_depth=depth)
    seen = _sample_calls(eng)
    before, ticks = _calls(), eng.stats["ticks"]
    rs = np.random.RandomState(5)
    for i, n in enumerate((4, 12, 30, 7, 9)):    # 30: two chunks of a prompt
        eng.add_request(Request(rs.randint(1, 64, size=n).astype(np.int32),
                                max_new_tokens=3 + 2 * i))
    eng.run()
    got = _delta(before)
    assert got["stochastic"] == 0
    assert len(seen) >= 2
    assert got["greedy"] == eng.stats["ticks"] - ticks + len(seen)


def test_stochastic_is_counted_only_while_a_sampling_request_runs(model,
                                                                  draws):
    """Three greedy requests of 12 tokens and one sampling request of 4 in
    one batch: the stochastic branch is counted for the call that chose
    the batch's first tokens and the three ticks the sampling request then
    ran, and the device drew exactly that often. Its slot keeps the
    temperature after it is freed; the ticks after count greedy."""
    eng = LLMEngine(model, seed=7, **ENG)
    before = _calls()
    rs = np.random.RandomState(6)
    rids = [eng.add_request(Request(
        rs.randint(1, 64, size=6).astype(np.int32),
        max_new_tokens=4 if i == 2 else 12,
        **({"temperature": 0.8} if i == 2 else {}))) for i in range(4)]
    TRACER.clear()
    TRACER.enable()
    try:
        eng.run()
    finally:
        TRACER.disable()
    spans = {n: sorted((e for e in TRACER.export()["traceEvents"]
                        if e["ph"] == "X" and e["name"] == n),
                       key=lambda e: e["ts"])
             for n in ("serving.decode", "serving.prefill")}
    TRACER.clear()
    eng.assert_quiescent()
    assert len(eng.requests[rids[2]].tokens) == 4
    assert (eng.temps > 0).any()                 # stale, and harmless
    got = _delta(before)
    assert got == {"stochastic": 4, "greedy": 8}   # 1 + 3, and ticks 4-11
    assert draws() == 4
    greedy = [e["args"]["greedy"] for e in spans["serving.decode"]]
    assert greedy == [False] * 3 + [True] * 8
    sampled = [e["args"]["greedy"] for e in spans["serving.prefill"]
               if "greedy" in e["args"]]
    assert sampled == [False]


def test_a_beam_only_tick_is_greedy(model):
    """Beam rows are forced to temperature 0 (their tokens come from the
    select over ``logp``), so an engine at a default temperature above 0
    that serves one beam request counts no stochastic tick."""
    eng = LLMEngine(model, seed=7, **{**ENG, "eos_token_id": 1},
                    temperature=0.9)
    before = _calls()
    rid = eng.add_request(Request(_prompts()[1], max_new_tokens=5,
                                  num_beams=2))
    eng.run()
    assert len(eng.requests[rid].tokens) == 5
    got = _delta(before)
    assert got["stochastic"] == 0 and got["greedy"] >= 4


# ------------------------------------------- (e) the host's key sequence
@pytest.mark.parametrize("sampling", [(), (1, 4), (0, 1, 2, 3, 4, 5)],
                         ids=["greedy", "mixed", "sampled"])
def test_the_key_sequence_does_not_depend_on_the_branch(model, sampling):
    """One ``next_key`` a sampler call whichever branch it takes: after a
    run the engine key is the chained split, once a call counted."""
    before = _calls()
    eng, _ = _run(model, sampling=sampling)
    calls = sum(_delta(before).values())
    key = jax.random.PRNGKey(7)
    for _ in range(calls):
        key = jax.random.split(key)[0]
    np.testing.assert_array_equal(np.asarray(eng.exe.rng), np.asarray(key))
    if not sampling:
        assert _delta(before)["stochastic"] == 0
