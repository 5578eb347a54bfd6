"""The tick's gauge sweep and key split run in the device's shadow (ISSUE 34).

Between a tick's decode dispatch and the fetch of its tokens the host only
waits, so that is where a tick sweeps its gauges (``serving.gauges``,
``shadow=True``) and where the executor splits the key of the next
consumer. The sweep publishes the tick's END state, which the host knows at
dispatch unless something leaves its place in the tick: such a tick sweeps
at its end (``shadow=False``), exactly. What every test here holds to is the
state a forced sweep would publish at that instant, and the chained
``jax.random.split`` sequence of before.

All CPU, tiny model, none timing-sensitive.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import METRICS, TRACER
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.utils.faults import FAULTS

ENG = dict(num_slots=4, block_size=4, max_prompt_len=16, max_seq_len=64,
           eos_token_id=None)
GAUGES = ("serving_queue_depth", "serving_active_slots",
          "serving_kv_blocks_in_use", "serving_kv_block_utilization",
          "serving_kv_bytes_per_token", "serving_kv_occupancy",
          "serving_prefix_hit_rate")
STATES = ("active", "parked", "cow_pending", "reserved", "free")


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64, dtype=jnp.float32)
    return LlamaForCausalLM(cfg)


def _prompts(n, seed=0, lo=3, hi=14):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 64, size=rs.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _published(eng):
    """What the gauges read now: the point-in-time ones of the sweep."""
    got = {g: METRICS.get(g).value() for g in GAUGES}
    got.update({s: METRICS.get("serving_kv_blocks").value(state=s)
                for s in STATES})
    got["resident_bpt"] = eng.kv.ledger.bytes_per_token
    return got


def _exact(eng):
    """The same after a forced sweep: what this instant's state reads."""
    eng._refresh_gauges(force=True)
    return _published(eng)


def _sweeps(tick):
    """One tick's ``serving.gauges`` spans -> their ``shadow`` args."""
    return [e["args"]["shadow"] for e in _spans("serving.gauges")
            if _within(e, tick)]


def _spans(name):
    return sorted((e for e in TRACER.export()["traceEvents"]
                   if e["ph"] == "X" and e["name"] == name),
                  key=lambda e: e["ts"])


def _within(e, outer):
    return (outer["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"])


def _step_traced(eng):
    """One ``step()`` under the tracer -> (its ``serving.step`` span, what
    the gauges read when it returned)."""
    TRACER.enable()
    try:
        eng.step()
    finally:
        TRACER.disable()
    return _spans("serving.step")[-1], _published(eng)


def test_sweep_sits_between_dispatch_and_fetch_once_a_tick(model):
    eng = LLMEngine(model, **ENG)
    for p in _prompts(3):
        eng.add_request(Request(p, max_new_tokens=8))
    eng.step()                       # admits and prefills; decodes once
    for _ in range(3):               # decode ticks that finish nothing
        tick, _ = _step_traced(eng)
        (sweep,) = [e for e in _spans("serving.gauges") if _within(e, tick)]
        (sent,) = [e for e in _spans("exe.decode_tick") if _within(e, tick)]
        (fetch,) = [e for e in _spans("serving.fetch") if _within(e, tick)]
        assert sweep["args"]["shadow"] is True
        assert sent["ts"] + sent["dur"] <= sweep["ts"]
        assert sweep["ts"] + sweep["dur"] <= fetch["ts"]
        book = [e for e in _spans("serving.bookkeeping") if _within(e, tick)]
        assert len(book) == 1 and not _within(sweep, book[0])
        TRACER.clear()


def test_gauges_after_every_step_read_as_a_forced_sweep_would(model):
    """A short backlog replay: more requests than slots, shared prefixes,
    lengths that end in different ticks. After every ``step()`` the gauges
    are what a forced sweep publishes then, whichever side of the fetch
    the tick swept on; and it swept once."""
    eng = LLMEngine(model, **ENG)
    head = _prompts(1, seed=5, lo=8, hi=9)[0]
    for i, p in enumerate(_prompts(9, seed=1)):
        prompt = np.concatenate([head, p[:6]]) if i % 2 else p
        eng.add_request(Request(prompt, max_new_tokens=3 + 2 * (i % 4)))
    shadowed = ended = 0
    while eng.has_work():
        before = eng._gauge_sweeps
        tick, got = _step_traced(eng)
        assert eng._gauge_sweeps == before + 1
        (shadow,) = _sweeps(tick)
        shadowed += shadow
        ended += not shadow
        assert got == _exact(eng)
        TRACER.clear()
    assert shadowed and ended        # the replay saw both kinds of tick
    assert METRICS.get("serving_active_slots").value() == 0
    eng.assert_quiescent()


def _decoding(model, **kw):
    """An engine two ticks into three answers of twelve tokens."""
    eng = LLMEngine(model, **{**ENG, **kw})
    for p in _prompts(3):
        eng.add_request(Request(p, max_new_tokens=12, deadline_s=50.0))
    eng.step()
    eng.step()
    return eng


def _row(eng, i):
    return eng.requests[int(eng.slot_req[np.nonzero(eng.active)[0][i]])]


def _finish(eng, clock):
    eng.max_gen[eng.active] = eng.gen[eng.active] + np.array([1, 5, 5])


def _cancel_in_stream(eng, clock):
    victim = _row(eng, -1).req_id
    _row(eng, 0).stream = lambda req, tok: eng.cancel(victim)


def _expire(eng, clock):
    clock[0] = 100.0


def _preempt(eng, clock):
    FAULTS.install("serving.preempt", times=1,
                   action=lambda ctx: ctx["engine"]._preempt())


@pytest.mark.parametrize("leave, sweeps, stat", [
    (_finish, [False], None),                   # foreseen: one sweep
    (_cancel_in_stream, [True, False], "cancelled"),
    (_expire, [False], "timeouts"),
    (_preempt, [False], "preemptions")])
def test_a_tick_that_loses_a_request_ends_in_an_exact_sweep(
        model, leave, sweeps, stat):
    clock = [0.0]
    eng = _decoding(model, preemption=True, clock=lambda: clock[0])
    leave(eng, clock)
    try:
        tick, got = _step_traced(eng)
    finally:
        FAULTS.clear()
    assert _sweeps(tick) == sweeps   # the last one the tick's own end
    assert got == _exact(eng)
    if stat is None:
        assert sum(r.done for r in eng.requests.values()) == 1
    else:
        assert eng.stats[stat] >= 1


def test_an_unforeseen_finish_sweeps_again_at_the_ticks_end(model):
    """EOS is what the tick samples: the host cannot know at dispatch, so
    the tick sweeps in the shadow and again, exactly, at its end."""
    probe = _decoding(model)
    slot = int(np.nonzero(probe.active)[0][0])
    probe.step()
    eos = int(probe.last_tok[slot])  # what that row samples in tick 3
    eng = _decoding(model, eos_token_id=eos)
    if not eng.active[slot]:
        pytest.skip("the row met that token earlier")
    tick, got = _step_traced(eng)
    assert _sweeps(tick) == [True, False]
    assert not eng.active[slot]
    assert got == _exact(eng)


def test_a_tick_without_a_decode_sweeps_at_its_end(model):
    eng = LLMEngine(model, prefill_only=True, **ENG)
    eng.add_request(Request(_prompts(1)[0], max_new_tokens=4))
    tick, got = _step_traced(eng)
    assert _sweeps(tick) == [False]
    assert got == _exact(eng)
    idle = LLMEngine(model, **ENG)   # nothing active: no dispatch either
    tick, got = _step_traced(idle)
    assert _sweeps(tick)[-1] is False
    assert got == _exact(idle)


@pytest.mark.parametrize("end", ["run", "drain"])
def test_run_and_drain_end_exact(model, end):
    eng = LLMEngine(model, **ENG)
    for i, p in enumerate(_prompts(6, seed=2)):
        eng.add_request(Request(p, max_new_tokens=2 + i))
    eng.step()
    out = eng.run() if end == "run" else eng.drain()
    assert len(out) == 6
    got = _published(eng)
    assert got == _exact(eng)
    assert got["serving_active_slots"] == got["serving_queue_depth"] == 0
    assert got["active"] == 0
    eng.assert_quiescent()


def _chain(seed, n):
    key, subs = jax.random.PRNGKey(seed), []
    for _ in range(n):
        key, sub = jax.random.split(key)
        subs.append(np.asarray(sub))
    return subs


def test_next_key_is_the_chained_split(model):
    """Ten keys across decode ticks (each splits ahead for the next
    consumer), a ``sample_rows`` and bare calls; then the same after
    ``exe.rng`` is assigned, which drops the pair held."""
    eng = LLMEngine(model, seed=7, temperature=0.8, **ENG)
    exe, seen = eng.exe, []
    real = exe.next_key
    exe.next_key = lambda: (seen.append(np.asarray(k := real())), k)[1]
    for p in _prompts(2):
        eng.add_request(Request(p, max_new_tokens=20))
    eng.step()                       # a prefill's sample_rows and a tick
    eng.step()
    assert exe._split_ahead is not None      # the tick split ahead
    exe.sample_rows(jnp.zeros((1, 64)), np.ones(1, np.float32),
                    np.ones(1, np.float32))
    exe.next_key()
    while len(seen) < 10:
        eng.step()
    want = _chain(7, len(seen))
    assert all((a == b).all() for a, b in zip(seen, want))
    eng.step()                       # holds a pair again
    del seen[:]
    exe.rng = jax.random.PRNGKey(11)
    assert exe._split_ahead is None
    for _ in range(3):
        eng.step()
    exe.next_key()
    want = _chain(11, len(seen))
    assert len(seen) == 4
    assert all((a == b).all() for a, b in zip(seen, want))


# the streams of this replay at the parent commit (6effae2), where every
# key was split when it was asked for
SAMPLED_DIGEST = (
    "3797dbde5365c6936997ecba4fee2dd3cdf3aca53ca1b20f7615b6c06faedef0")


def test_seeded_sampling_streams_are_the_parents(model):
    eng = LLMEngine(model, seed=3, temperature=0.8, top_p=0.95, **ENG)
    for i, p in enumerate(_prompts(7, seed=4)):
        eng.add_request(Request(p, max_new_tokens=4 + 3 * (i % 3)))
    out = eng.run()
    text = ";".join(f"{rid}:{','.join(map(str, toks))}"
                    for rid, toks in sorted(out.items()))
    assert hashlib.sha256(text.encode()).hexdigest() == SAMPLED_DIGEST
