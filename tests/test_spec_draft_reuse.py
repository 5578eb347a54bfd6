"""Spec-draft reuse from the radix frontier (ISSUE 11, closing PR 9's
REMAINING): a radix prefix hit used to pay a draft-side re-prefill of
the whole adopted span, counted as ``replay_prefill`` waste. The engine
now seeds ``draft_cur`` from the slot's resident draft cache, so the
catch-up feed embeds only the un-adopted suffix — asserted through the
goodput ledger, the reuse counter and output identity."""
import numpy as np
import pytest

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.goodput import GOODPUT
from paddle_tpu.serving import LLMEngine, Request
from paddle_tpu.serving.telemetry import _SPEC_DRAFT_REUSE

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def _run(eng, prompts, max_new=8):
    for p in prompts:
        eng.add_request(Request(p, max_new_tokens=max_new))
    return {r: list(map(int, t)) for r, t in eng.run().items()}


def _kw(model, **kw):
    # one slot: the second request is guaranteed to land on the slot
    # whose draft cache holds the first request's prefix
    base = dict(num_slots=1, block_size=8, max_prompt_len=16,
                max_seq_len=96, draft_model=model, prefix_caching=True)
    base.update(kw)
    return base


def _two_phase(model, rs, **ekw):
    """Two sequential requests sharing a 24-token prefix; returns
    (outputs, reuse tokens, replay_prefill waste of phase 2)."""
    shared = rs.randint(0, 64, (24,))
    p1 = np.concatenate([shared, rs.randint(0, 64, (4,))])
    p2 = np.concatenate([shared, rs.randint(0, 64, (4,))])
    eng = LLMEngine(model, **_kw(model, **ekw))
    o1 = _run(eng, [p1])
    w0 = GOODPUT.waste_by_why().get("replay_prefill", 0)
    r0 = _SPEC_DRAFT_REUSE.value()
    o2 = _run(eng, [p2])
    replay = GOODPUT.waste_by_why().get("replay_prefill", 0) - w0
    reuse = _SPEC_DRAFT_REUSE.value() - r0
    return {**o1, **o2}, reuse, replay


def test_radix_hit_seeds_draft_and_kills_replay_waste(model):
    """A radix hit with a draft model seeds the whole adopted span: its
    draft re-embed disappears from the waste ledger. Without a radix hit
    (``prefix_caching=False``) nothing is seeded — and the outputs are
    identical either way (reuse can only change speed, never tokens)."""
    out_on, reuse_on, replay_on = _two_phase(
        model, np.random.RandomState(3))
    assert reuse_on == 24          # the shared span, token for token
    assert replay_on == 0          # none of it re-embedded draft-side

    out_off, reuse_off, _ = _two_phase(
        model, np.random.RandomState(3), prefix_caching=False)
    assert reuse_off == 0
    assert list(out_on.values()) == list(out_off.values())


def test_unrelated_prompt_reuses_nothing(model):
    """No shared prefix → no radix adoption → seeding must stay at 0
    even though the slot's resident draft cache is warm."""
    rs = np.random.RandomState(4)
    eng = LLMEngine(model, **_kw(model))
    _run(eng, [rs.randint(0, 64, (24,))])
    r0 = _SPEC_DRAFT_REUSE.value()
    _run(eng, [rs.randint(0, 64, (24,))])
    assert _SPEC_DRAFT_REUSE.value() == r0


def test_reuse_with_unrelated_draft_model(model):
    """A near-zero-acceptance draft stresses the rollback/snapshot path:
    resident snapshots must track the COMMITTED prefix, so the second
    request still reuses and still matches the no-reuse outputs."""
    dcfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                            num_attention_heads=4, num_key_value_heads=2,
                            vocab_size=64)
    draft = LlamaForCausalLM(dcfg)
    out_on, reuse, _ = _two_phase(model, np.random.RandomState(5),
                                  draft_model=draft)
    assert reuse > 0
    # identity vs a spec-less engine: reuse composes with rejection
    rs = np.random.RandomState(5)
    shared = rs.randint(0, 64, (24,))
    p1 = np.concatenate([shared, rs.randint(0, 64, (4,))])
    p2 = np.concatenate([shared, rs.randint(0, 64, (4,))])
    plain = LLMEngine(model, **{**_kw(model), "draft_model": None})
    base = {**_run(plain, [p1]), **_run(plain, [p2])}
    assert list(out_on.values()) == list(base.values())


def test_goodput_reconciliation_still_exact(model):
    """saved/waste are side ledgers: reuse accounting must not break the
    good-token vs serving_tokens_total reconciliation."""
    from paddle_tpu.serving.telemetry import _TOKENS
    rs = np.random.RandomState(6)
    shared = rs.randint(0, 64, (24,))
    prompts = [np.concatenate([shared, rs.randint(0, 64, (4,))])
               for _ in range(3)]
    t0 = _TOKENS.value()
    g0 = GOODPUT.good_total()
    eng = LLMEngine(model, **_kw(model))
    out = _run(eng, prompts)
    good = GOODPUT.good_total() - g0
    emitted = sum(len(t) for t in out.values())
    assert good == emitted == _TOKENS.value() - t0
