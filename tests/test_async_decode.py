"""Async pipelined decode (ISSUE 20): depth-K deferred-sync decode loop.

* bit-identity at depth ∈ {1, 2, 4} vs depth 0 — greedy, temperature,
  temperature+EOS (rng rewind over the masked suffix), chunked prefill,
  preemption/replay chaos, radix prefix adoption
* forced per-tick drains for grammar slots and spec-decode ticks (the
  pipeline de-pipelines for THAT tick, never permanently)
* device stop mask at the exact EOS boundary: a lone slot bills zero
  ``async_overrun`` waste
* ``serving.tick`` chaos mid-window: exception-atomic drain, identical
  mid-fault and final streams, pool quiescent
* ``async_depth=0`` traces the synchronous program alone
  (breadcrumb-guarded)
* ``async_overrun`` arithmetic: a stream-callback cancel mid-cruise
  bills exactly ``depth`` over-dispatched rows
* satellite: spec-decode host sampling gathers only non-greedy rows
  (fetched byte count asserted), ``PT_GAUGE_EVERY_S`` sweep throttle
  with exact forced sweeps at finish/run()-end boundaries
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged import clear_jit_caches
from paddle_tpu.observability import GOODPUT, METRICS
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import DegradationController, LLMEngine, Request
from paddle_tpu.utils.faults import FAULTS, InjectedFault


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def draft():
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def _mk(model, **kw):
    args = dict(num_slots=3, block_size=4, max_prompt_len=16,
                max_seq_len=64, seed=7)
    args.update(kw)
    return LLMEngine(model, **args)


def _prompts(rs, n=6, lo=3, hi=14):
    return [rs.randint(2, 64, (int(l),))
            for l in rs.randint(lo, hi, size=n)]


def _run(eng, prompts, new=10, **rkw):
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=new, **rkw))
    out = eng.run()
    eng.assert_quiescent()
    return {r: list(map(int, t)) for r, t in out.items()}


def _spec_gate(level):
    """A controller held at ``level`` (L1+: speculation off): no signals
    and infinite down-patience, so the engine's polls never move it."""
    c = DegradationController(signals=[], down_patience=10 ** 9)
    c.force_level(level)
    return c


def _drains():
    c = METRICS.get("serving_async_drains_total")
    return {k[0]: v[0] for k, v in c._series.items()}


# ------------------------------------------------------- bit-identity
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_bit_identity_greedy_temperature_eos(model, depth):
    rs = np.random.RandomState(3)
    prompts = _prompts(rs)
    for kw in (dict(), dict(temperature=0.8),
               dict(temperature=0.8, eos_token_id=1)):
        base = _run(_mk(model, **kw), prompts)
        got = _run(_mk(model, async_depth=depth, **kw), prompts)
        assert got == base, (depth, kw)
    # the pipeline actually engaged (drains observed, depth gauge set)
    assert sum(_drains().values()) > 0
    assert METRICS.get("serving_async_depth").value() == depth


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_bit_identity_chunked_prefill(model, depth):
    rs = np.random.RandomState(5)
    prompts = [rs.randint(2, 64, (40,)), rs.randint(2, 64, (9,)),
               rs.randint(2, 64, (25,))]
    kw = dict(num_slots=2, max_prompt_len=8)
    base = _run(_mk(model, **kw), prompts, new=8)
    got = _run(_mk(model, async_depth=depth, **kw), prompts, new=8)
    assert got == base


@pytest.mark.chaos
def test_bit_identity_preempt_replay_chaos(model):
    rs = np.random.RandomState(3)
    prompts = _prompts(rs)

    def run(depth):
        FAULTS.clear()
        FAULTS.install("serving.preempt", every=4, times=3,
                       action=lambda ctx: ctx["engine"]._preempt())
        eng = _mk(model, num_slots=2, max_seq_len=48, preemption=True,
                  async_depth=depth)
        out = _run(eng, prompts)
        FAULTS.clear()
        assert eng.stats["preemptions"] > 0
        return out

    base = run(0)
    for depth in (1, 2):
        assert run(depth) == base, depth


def test_bit_identity_radix_adoption(model):
    """Two waves of shared-prefix prompts: the second wave adopts
    committed blocks from the radix trie mid-pipeline."""
    rs = np.random.RandomState(11)
    stem = rs.randint(2, 64, (10,))
    waves = [np.concatenate([stem, rs.randint(2, 64, (int(k),))])
             for k in (3, 5, 2)]

    def run(depth):
        eng = _mk(model, prefix_caching=True, async_depth=depth)
        first = _run(eng, [stem], new=6)
        second = {}
        for p in waves:
            rid = eng.add_request(Request(p, max_new_tokens=6))
            second.update({r: list(map(int, t))
                           for r, t in eng.run().items() if r == rid})
        eng.assert_quiescent()
        saved = GOODPUT.saved_total()
        return first, second, saved

    b1, b2, bsaved = run(0)
    g1, g2, gsaved = run(2)
    assert (g1, g2) == (b1, b2)
    assert bsaved > 0 and gsaved > bsaved  # adoption really happened


# ------------------------------------------------------- forced drains
def test_grammar_slot_forces_per_tick_drain(model):
    """A grammar-constrained slot must see the host automaton before
    every next token: while one is live the engine never runs ahead
    (window empty every tick), a mid-cruise grammar arrival drains the
    standing window first, and the streams stay identical."""
    from paddle_tpu.serving.grammar import TokenMaskAutomaton
    vocab = [chr(ord("a") + i % 26) for i in range(63)] + [""]
    aut = TokenMaskAutomaton("[ab]{6}", vocab=vocab, eos_token_id=63)
    rs = np.random.RandomState(4)
    plain = rs.randint(2, 64, (6,))
    gram = rs.randint(2, 64, (5,))

    def run(depth):
        eng = _mk(model, eos_token_id=63, async_depth=depth,
                  block_size=16, max_seq_len=64)
        state = {}

        def arrive(req, tok):
            # token 8 lands mid-cruise (the first ticks drain inside the
            # admission/prefill step itself, before any window forms)
            if len(req.tokens) == 8 and "r1" not in state:
                state["r1"] = eng.add_request(
                    Request(gram, max_new_tokens=6, grammar=aut))

        eng.add_request(Request(plain, max_new_tokens=12, stream=arrive))
        cruised = False
        while eng.has_work():
            eng.step()
            cruised = cruised or bool(eng._async_win)
            if depth and eng._grammar:
                assert not eng._async_win    # grammar => per-tick drain
        eng.assert_quiescent()
        assert "r1" in state                 # arrival really happened
        if depth:
            assert cruised                   # pipeline engaged pre-arrival
        return {r: list(map(int, q.tokens)) for r, q in eng.requests.items()}

    base = run(0)
    got = run(2)
    assert got == base
    assert _drains().get("admit", 0) > 0     # arrival drained the window


def test_spec_tick_forces_drain_not_permanent_depipelining(model, draft):
    rs = np.random.RandomState(6)
    prompts = _prompts(rs, n=4)

    def run(depth):
        eng = _mk(model, draft_model=draft, spec_k=3, async_depth=depth)
        out = _run(eng, prompts, new=8)
        assert eng.stats["spec_ticks"] > 0     # spec still runs at depth>0
        return out, eng.stats["spec_ticks"]

    base, bticks = run(0)
    got, gticks = run(2)
    assert got == base
    assert gticks == bticks                    # same spec cadence, any depth


def test_spec_toggle_mid_cruise_drains_with_why_spec(model, draft):
    """``degrade.spec_enabled()`` turning true while the pipeline is
    cruising: the next step must drain the standing window (why=spec)
    before the spec tick runs — and greedy spec identity keeps the
    stream bit-equal to the never-spec baseline."""
    rs = np.random.RandomState(7)
    p = rs.randint(2, 64, (6,))
    kw = dict(num_slots=1, block_size=16, max_seq_len=64,
              draft_model=draft, spec_k=3)
    never = _mk(model, degrade=_spec_gate(1), **kw)
    base = _run(never, [p], new=12)
    assert never.stats["spec_ticks"] == 0

    gate = _spec_gate(1)

    def flip(req, tok):
        if len(req.tokens) == 3:
            gate.force_level(0)

    eng = _mk(model, async_depth=2, degrade=gate, **kw)
    eng.add_request(Request(p, max_new_tokens=12, stream=flip))
    out = eng.run()
    eng.assert_quiescent()
    assert {r: list(map(int, t)) for r, t in out.items()} == base
    assert _drains().get("spec", 0) > 0
    assert eng.stats["spec_ticks"] > 0         # spec engaged after the flip


# ----------------------------------------------------- EOS stop mask
def test_eos_stop_mask_exact_boundary_no_overrun(model):
    """Lone slot, natural EOS: the device stop mask must catch the
    boundary inside the jit — over-dispatched ticks run fully masked
    (never billed as waste) and the rng rewind leaves the key stream
    exactly where the synchronous loop ends."""
    rs = np.random.RandomState(9)
    p = rs.randint(2, 64, (7,))
    probe = _run(_mk(model, num_slots=1), [p], new=10)
    eos = next(iter(probe.values()))[4]        # a token greedy really emits

    def run(depth):
        eng = _mk(model, num_slots=1, eos_token_id=eos, async_depth=depth)
        out = _run(eng, [p], new=10)
        (req,) = eng.requests.values()
        assert req.finish_reason == "eos"      # the boundary was exercised
        return out

    base = run(0)
    for depth in (1, 2, 4):
        assert run(depth) == base, depth
    assert GOODPUT.waste_by_why().get("async_overrun", 0) == 0


# ------------------------------------------------------------ chaos
@pytest.mark.chaos
def test_tick_chaos_mid_window_exception_atomic(model):
    """A serving.tick fault raised while ticks are in flight must drain
    the window first (why=exception): the request state at the moment
    the fault surfaces — and after recovery — is bit-identical to the
    synchronous engine's, and the pool is clean."""
    rs = np.random.RandomState(3)
    prompts = _prompts(rs, n=2)

    def run(depth):
        FAULTS.clear()
        FAULTS.install("serving.tick", on={5}, exc=InjectedFault)
        eng = _mk(model, num_slots=2, block_size=16, max_seq_len=64,
                  async_depth=depth)
        for p in prompts:
            eng.add_request(Request(p, max_new_tokens=10))
        mid = None
        try:
            while eng.has_work():
                eng.step()
        except InjectedFault:
            mid = {r: list(map(int, q.tokens))
                   for r, q in eng.requests.items()}
            while eng.has_work():          # recover past the fault
                eng.step()
        FAULTS.clear()
        eng.assert_quiescent()
        assert mid is not None             # the fault really fired
        out = {r: list(map(int, q.tokens)) for r, q in eng.requests.items()}
        return mid, out

    b_mid, b_out = run(0)
    for depth in (1, 2):
        g_mid, g_out = run(depth)
        assert g_mid == b_mid, depth       # drained atomically at the fault
        assert g_out == b_out, depth
    assert _drains().get("exception", 0) > 0


# --------------------------------------------------------- depth zero
def test_depth_zero_traces_the_sync_program_alone(model):
    """``async_depth=0`` (the default) never traces the async tick
    program (breadcrumb-guarded) and never forms a window; depth 2
    traces its twin and emits the bit-exact stream."""
    rs = np.random.RandomState(13)
    prompts = _prompts(rs, n=4)
    clear_jit_caches()
    pa._trace_events.clear()
    eng = _mk(model, async_depth=0)
    base = _run(eng, prompts)
    assert "tick:async" not in pa._trace_events  # the sync program only
    assert sum(_drains().values()) == 0          # no window ever formed

    clear_jit_caches()
    pa._trace_events.clear()
    got = _run(_mk(model, async_depth=2), prompts)
    assert got == base
    assert "tick:async" in pa._trace_events    # pipeline traced its twin


def test_async_depth_validation(model):
    with pytest.raises(ValueError, match="async_depth"):
        _mk(model, async_depth=-1)


# ----------------------------------------------------- overrun ledger
def test_async_overrun_arithmetic_exact(model):
    """Cancel fired from a stream callback mid-cruise: the already
    dispatched window ticks keep computing the dead slot — exactly
    ``depth`` rows bill ``async_overrun``, and the cancelled stream is
    bit-identical to the synchronous engine under the same callback."""
    rs = np.random.RandomState(8)
    pa_, pb = rs.randint(2, 64, (4,)), rs.randint(2, 64, (5,))
    depth = 3

    def run(d):
        eng = _mk(model, num_slots=2, block_size=16, max_seq_len=64,
                  async_depth=d)
        state = {}

        def cb(req, tok):
            if len(req.tokens) == 3:
                eng.cancel(state["rb"], reason="cancelled")

        ra = eng.add_request(Request(pa_, max_new_tokens=8, stream=cb))
        state["rb"] = eng.add_request(Request(pb, max_new_tokens=8))
        eng.run()
        eng.assert_quiescent()
        assert eng.requests[state["rb"]].finish_reason == "cancelled"
        return {r: list(map(int, q.tokens)) for r, q in
                eng.requests.items()}

    base = run(0)
    assert GOODPUT.waste_by_why().get("async_overrun", 0) == 0
    got = run(depth)
    assert got == base
    assert GOODPUT.waste_by_why().get("async_overrun", 0) == depth


# ------------------------------------- satellite: spec fetch gathering
def test_spec_fetch_bytes_gathers_only_nongreedy_rows(model, draft):
    """Host spec sampling must fetch the full [rows, V] block only for
    the NON-greedy rows (gathered on device); greedy rows ride the [ns]
    argmax fetch. Byte count asserted exactly."""
    rs = np.random.RandomState(2)
    eng = _mk(model, draft_model=draft, spec_k=3, num_slots=2,
              degrade=_spec_gate(1))              # admit via the plain tick
    r0 = eng.add_request(Request(rs.randint(2, 64, (5,)),
                                 max_new_tokens=8))
    r1 = eng.add_request(Request(rs.randint(2, 64, (6,)),
                                 max_new_tokens=8, temperature=0.7))
    eng.step()
    eng._spec_fetch_bytes = 0
    staged = [(0, r0, 3), (1, r1, 3)]
    seqs = {s: eng._committed_seq(s) for s in (0, 1)}
    props, _ = eng._spec_draft(staged, seqs)
    assert len(props[0]) == 3 and len(props[1]) == 3
    ns, V, k = 2, 64, 3
    am_item = jnp.argmax(jnp.zeros((2, 2), jnp.float32), axis=-1) \
        .dtype.itemsize
    # 3 pick_all calls (steady + 2 rounds), each: [ns] argmax ints for
    # the greedy row + ONE gathered [1, V] f32 row for the temp slot
    want = k * (ns * am_item + 1 * V * 4)
    assert eng._spec_fetch_bytes == want
    assert want < k * ns * V * 4              # vs the old full-block fetch

    # all-greedy staging never fetches a V-wide row at all
    eng._spec_fetch_bytes = 0
    eng.temps[1] = 0.0
    eng._spec_draft(staged, {s: eng._committed_seq(s) for s in (0, 1)})
    assert eng._spec_fetch_bytes == k * ns * am_item


# --------------------------------------- satellite: gauge sweep throttle
def test_gauge_throttle_skips_sweeps_forces_boundaries(model, monkeypatch):
    rs = np.random.RandomState(3)
    prompts = _prompts(rs)
    eng = _mk(model)
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=8))
    while eng.has_work():
        eng.step()
    default_sweeps, ticks = eng._gauge_sweeps, eng.stats["ticks"]
    assert default_sweeps >= ticks            # default: every tick, unchanged

    monkeypatch.setenv("PT_GAUGE_EVERY_S", "3600")
    eng2 = _mk(model)
    for p in prompts:
        eng2.add_request(Request(p, max_new_tokens=8))
    out = eng2.run()
    assert len(out) == len(prompts)
    assert eng2._gauge_sweeps < default_sweeps   # the throttle really bit
    # boundary exactness: run()-end forced sweep published final state
    assert METRICS.get("serving_active_slots").value() == 0
    assert METRICS.get("serving_queue_depth").value() == 0
    eng2.assert_quiescent()


def test_gauge_throttle_async_combo(model, monkeypatch):
    """Depth-2 pipeline + throttled sweep still emits the bit-identical
    stream."""
    rs = np.random.RandomState(3)
    prompts = _prompts(rs)
    base = _run(_mk(model), prompts)
    monkeypatch.setenv("PT_GAUGE_EVERY_S", "3600")
    got = _run(_mk(model, async_depth=2), prompts)
    assert got == base
