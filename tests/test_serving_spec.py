"""Speculative decoding inside the engine (ISSUE 5): greedy output
identity with a draft model in the loop, composition with preemption
chaos, exception-atomicity of the ``serving.spec_verify`` fault site,
the degrade gate, adaptive-k behaviour, and the metric
surface (proposed/accepted counters + acceptance-rate gauge)."""
import numpy as np
import pytest

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import DegradationController, LLMEngine, Request
from paddle_tpu.utils.faults import FAULTS

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def draft():
    # an unrelated tiny model: near-zero acceptance, which stresses the
    # reject/rewind path far harder than a well-matched draft would
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def _prompts(n, rs, lo=3, hi=12):
    return [rs.randint(0, 64, (int(l),)) for l in rs.randint(lo, hi, size=n)]


def _run(eng, prompts, max_new=10, **kw):
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=max_new, **kw))
    out = eng.run()
    return {rid: list(map(int, t)) for rid, t in out.items()}


def _baseline(model, prompts, max_new=10, **ekw):
    kw = dict(num_slots=4, block_size=8, max_prompt_len=16, max_seq_len=64)
    kw.update(ekw)
    return _run(LLMEngine(model, **kw), prompts, max_new)


# ------------------------------------------------------ greedy identity

@pytest.mark.parametrize("which_draft", ["unrelated", "self"])
def test_greedy_spec_identical_to_nonspec(model, draft, which_draft):
    """Token-for-token identity at temperature 0, at both extremes of
    draft quality: an unrelated draft (everything rejected — pure rewind
    exercise) and draft==target (everything accepted — pure multi-commit
    exercise)."""
    rs = np.random.RandomState(0)
    prompts = _prompts(6, rs)
    base = _baseline(model, prompts)
    d = model if which_draft == "self" else draft
    eng = LLMEngine(model, draft_model=d, spec_k=4, num_slots=4,
                    block_size=8, max_prompt_len=16, max_seq_len=64)
    spec = _run(eng, prompts)
    assert spec == base
    eng.assert_quiescent()
    assert eng.stats["spec_ticks"] > 0
    assert eng.stats["spec_proposed"] > 0
    if which_draft == "self":
        # draft == target: greedy proposals are the target argmax chain
        assert eng.stats["spec_accepted"] == eng.stats["spec_proposed"]


def test_greedy_spec_identical_under_preemption_chaos(model):
    """The acceptance-criteria schedule: induced preemptions evict
    mid-spec requests (draft cache frontier reset), replay rebuilds
    them, and outputs stay exactly the greedy chain."""
    rs = np.random.RandomState(10)
    prompts = _prompts(5, rs, lo=4, hi=12)
    base = _baseline(model, prompts, max_new=8,
                     num_slots=2, block_size=4, max_seq_len=32,
                     preemption=True)

    # speculation collapses a wave to ~2 ticks, so the cadence must be
    # tight or the schedule exhausts the run before ever firing
    FAULTS.clear()
    FAULTS.install("serving.preempt", every=2, times=8,
                   action=lambda ctx: ctx["engine"]._preempt())
    eng = LLMEngine(model, draft_model=model, spec_k=4, num_slots=2,
                    block_size=4, max_prompt_len=16, max_seq_len=32,
                    preemption=True)
    spec = _run(eng, prompts, max_new=8)
    assert eng.stats["preemptions"] > 0, "schedule never fired"
    assert spec == base
    eng.assert_quiescent()


def test_spec_with_tight_block_pool_preempt_replay(model):
    """A pool too small for all slots forces organic evict/replay while
    speculation is staging multi-block reservations."""
    rs = np.random.RandomState(3)
    prompts = _prompts(5, rs)
    base = _baseline(model, prompts, max_new=12, num_slots=4,
                     block_size=4, num_blocks=18, preemption=True,
                     max_seq_len=48)
    eng = LLMEngine(model, draft_model=model, spec_k=4, num_slots=4,
                    block_size=4, num_blocks=18, max_prompt_len=16,
                    max_seq_len=48, preemption=True)
    spec = _run(eng, prompts, max_new=12)
    assert spec == base
    eng.assert_quiescent()


def test_spec_composes_with_chunked_prefill(model):
    """Prompts longer than max_prompt_len chunk-prefill in; the slot's
    first spec round then catch-up-feeds the whole committed sequence
    into the empty draft cache before proposing."""
    rs = np.random.RandomState(6)
    prompts = _prompts(4, rs, lo=14, hi=30)
    base = _baseline(model, prompts, num_slots=2, block_size=4,
                     max_prompt_len=8, max_seq_len=48)
    eng = LLMEngine(model, draft_model=model, spec_k=4, num_slots=2,
                    block_size=4, max_prompt_len=8, max_seq_len=48)
    spec = _run(eng, prompts)
    assert spec == base
    assert eng.stats["spec_ticks"] > 0
    eng.assert_quiescent()


# --------------------------------------------------- chaos: spec_verify

def test_spec_verify_fault_is_exception_atomic(model):
    """An injected fault mid-verify must (a) not leak blocks, (b) fall
    back to the one-token tick for that round, (c) leave outputs exactly
    the non-spec greedy chain."""
    rs = np.random.RandomState(0)
    prompts = _prompts(5, rs)
    base = _baseline(model, prompts)
    FAULTS.clear()
    FAULTS.install("serving.spec_verify", every=2, times=4)
    eng = LLMEngine(model, draft_model=model, spec_k=4, num_slots=4,
                    block_size=8, max_prompt_len=16, max_seq_len=64)
    spec = _run(eng, prompts)
    assert eng.stats["spec_fallbacks"] > 0, "fault never fired"
    assert spec == base
    eng.assert_quiescent()          # no leaked blocks / reservations
    from paddle_tpu.observability import METRICS
    snap = METRICS.snapshot()["counters"]
    assert snap['faults_injected_total{site="serving.spec_verify"}'] > 0
    assert snap["serving_spec_fallbacks_total"] >= eng.stats["spec_fallbacks"]


# ----------------------------------------------------------------- gating

def test_degrade_gate_off_then_on_between_runs(model):
    """``degrade.spec_enabled()`` is asked every tick: one live engine
    with a draft model never drafts while it says no, drafts again once
    it says yes, and emits the non-spec stream both times."""
    gate = DegradationController(signals=[], down_patience=10 ** 9)
    gate.force_level(1)
    rs = np.random.RandomState(0)
    prompts = _prompts(4, rs)
    base = _baseline(model, prompts)
    eng = LLMEngine(model, draft_model=model, spec_k=4, num_slots=4,
                    block_size=8, max_prompt_len=16, max_seq_len=64,
                    degrade=gate)
    off = _run(eng, prompts)
    assert eng.stats["spec_ticks"] == 0
    assert list(off.values()) == list(base.values())
    gate.force_level(0)
    on = _run(eng, prompts)
    assert eng.stats["spec_ticks"] > 0
    # run() returns every request the engine has served: the second four
    assert list(on.values())[len(prompts):] == list(base.values())
    eng.assert_quiescent()


def test_beam_requests_never_speculate(model):
    """Beam search is spec-disabled per request; a mixed batch keeps
    greedy requests speculating while the beam request matches the
    non-spec engine's beam output."""
    rs = np.random.RandomState(5)
    prompts = _prompts(3, rs)

    def run(eng):
        eng.add_request(Request(prompts[0], max_new_tokens=8, num_beams=2))
        for p in prompts[1:]:
            eng.add_request(Request(p, max_new_tokens=8))
        out = eng.run()
        return {rid: list(map(int, t)) for rid, t in out.items()}

    e0 = LLMEngine(model, num_slots=6, block_size=8, max_prompt_len=16,
                   max_seq_len=64)
    base = run(e0)
    e1 = LLMEngine(model, draft_model=model, spec_k=4, num_slots=6,
                   block_size=8, max_prompt_len=16, max_seq_len=64)
    spec = run(e1)
    assert spec == base
    assert e1.stats["spec_ticks"] > 0       # the greedy rows did speculate
    e1.assert_quiescent()


# --------------------------------------------------- sampling / adaptive

def test_stochastic_spec_runs_and_respects_budgets(model):
    """temperature > 0 through the accept/reject/resample path: lengths
    honour max_new_tokens and the engine drains clean. (Distributional
    equivalence of the rule itself is covered by the seeded
    speculative_sample statistical test.)"""
    rs = np.random.RandomState(1)
    prompts = _prompts(5, rs)
    eng = LLMEngine(model, draft_model=model, spec_k=4, num_slots=4,
                    block_size=8, max_prompt_len=16, max_seq_len=64)
    out = _run(eng, prompts, max_new=12, temperature=0.8, top_p=0.95)
    assert all(len(v) == 12 for v in out.values())
    assert eng.stats["spec_accepted"] > 0    # draft==target: plenty accepted
    eng.assert_quiescent()


def test_adaptive_k_shrinks_on_bad_draft(model, draft):
    """With an unrelated draft nearly everything is rejected, so the
    per-slot EMA must drive k to the floor; with draft==target it must
    stay at the ceiling."""
    rs = np.random.RandomState(2)
    prompts = _prompts(4, rs)
    bad = LLMEngine(model, draft_model=draft, spec_k=4, num_slots=4,
                    block_size=8, max_prompt_len=16, max_seq_len=96)
    _run(bad, prompts, max_new=24)
    good = LLMEngine(model, draft_model=model, spec_k=4, num_slots=4,
                     block_size=8, max_prompt_len=16, max_seq_len=96)
    _run(good, prompts, max_new=24)
    bad_rate = bad.stats["spec_accepted"] / max(bad.stats["spec_proposed"], 1)
    good_rate = (good.stats["spec_accepted"]
                 / max(good.stats["spec_proposed"], 1))
    assert good_rate == 1.0
    assert bad_rate < 0.5
    # adaptive k throttled drafting: fewer proposals per spec tick
    assert (bad.stats["spec_proposed"] / bad.stats["spec_ticks"]
            < good.stats["spec_proposed"] / good.stats["spec_ticks"])


def test_spec_metrics_exported(model):
    rs = np.random.RandomState(0)
    prompts = _prompts(3, rs)
    eng = LLMEngine(model, draft_model=model, spec_k=4, num_slots=4,
                    block_size=8, max_prompt_len=16, max_seq_len=64)
    _run(eng, prompts)
    from paddle_tpu.observability import METRICS
    snap = METRICS.snapshot()
    assert snap["counters"]["serving_spec_proposed_total"] > 0
    assert snap["counters"]["serving_spec_accepted_total"] > 0
    assert 0.0 <= snap["gauges"]["serving_spec_acceptance_rate"] <= 1.0
    hist = [k for k in snap.get("histograms", {})
            if k.startswith("serving_spec_tokens_per_tick")]
    assert hist, "tokens-per-tick histogram missing"


# --------------------------------------------------------- ctor gating

def test_spec_rejects_vocab_mismatch(model):
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=32)
    with pytest.raises(ValueError):
        LLMEngine(model, draft_model=LlamaForCausalLM(cfg), num_slots=2,
                  block_size=8, max_prompt_len=16, max_seq_len=64)
