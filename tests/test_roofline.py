"""Serving roofline ledger (ISSUE 12): peak tables and device-kind
detection (including the empty-kind fix on ``chip_peak_flops``), the
analytic per-phase FLOPs/bytes models and their verdicts, the
``record_serving_throughput`` choke point, the engine's decode-tick
anatomy (breakdown histogram reconciling with ``serving_tick_seconds``
tick-for-tick, by construction), and the acceptance criterion: the
bench-shaped engine exports a nonzero bandwidth-bound
``serving_mbu{decode}`` under ``PT_ROOFLINE_KIND`` while a plain CPU
run exports 0.0 (undefined, never fabricated)."""
import math

import numpy as np
import pytest

from paddle_tpu.observability.flops import PEAK_BF16, chip_peak_flops
from paddle_tpu.observability.metrics import METRICS
from paddle_tpu.observability.roofline import (
    PEAK_HBM_BPS, ModelGeometry, arith_intensity, chip_peak_hbm_bw,
    kv_bytes_per_position, phase_bytes, phase_flops,
    record_serving_throughput, reset_serving_roofline,
    resolve_serving_peaks, roofline_verdict, serving_roofline_report,
    weight_bytes)


@pytest.fixture(autouse=True)
def _clean_roofline():
    reset_serving_roofline()
    yield
    reset_serving_roofline()


class _Dev:
    def __init__(self, kind="", platform=""):
        self.device_kind = kind
        self.platform = platform


# ------------------------------------------------------------- peak tables
def test_peak_tables_cover_the_same_chips():
    assert set(PEAK_HBM_BPS) == set(PEAK_BF16)
    assert PEAK_HBM_BPS["TPU v5 lite"] == pytest.approx(819e9)
    assert PEAK_HBM_BPS["TPU v5p"] == pytest.approx(2765e9)


@pytest.mark.parametrize("kind,bw", [
    ("TPU v5 lite", 819e9), ("TPU v5e", 819e9), ("TPU v5p", 2765e9),
    ("TPU v4", 1228e9), ("TPU v6", 1640e9),
    ("cpu", 0.0), ("NVIDIA H100", 0.0),
])
def test_chip_peak_hbm_bw_by_kind(kind, bw):
    assert chip_peak_hbm_bw(kind=kind) == pytest.approx(bw)


def test_unknown_tpu_kind_raises():
    """A TPU that is not in the tables is an error, never a v5e by
    default — on both tables."""
    with pytest.raises(ValueError, match="TPU v99"):
        chip_peak_hbm_bw(kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        chip_peak_flops(kind="TPU v99")


def test_empty_kind_is_undefined_not_v5e():
    """The satellite fix: an empty device_kind with no evidence of a TPU
    platform must yield 0.0 (undefined), not a fabricated v5e peak —
    on both tables."""
    assert chip_peak_flops(kind="") == 0.0
    assert chip_peak_hbm_bw(kind="") == 0.0
    assert chip_peak_flops(_Dev()) == 0.0          # mock with empty attrs
    assert chip_peak_hbm_bw(_Dev()) == 0.0
    assert chip_peak_flops(object()) == 0.0        # no attrs at all
    assert chip_peak_hbm_bw(object()) == 0.0
    assert chip_peak_flops(None) == 0.0
    assert chip_peak_hbm_bw(None) == 0.0


def test_tpu_platform_with_empty_kind_raises():
    """A device that says platform=tpu but reports no kind string IS a
    TPU of unknown peak — an error, not a v5e."""
    dev = _Dev(kind="", platform="tpu")
    with pytest.raises(ValueError):
        chip_peak_flops(dev)
    with pytest.raises(ValueError):
        chip_peak_hbm_bw(dev)


def test_non_tpu_platform_is_undefined_even_with_tpu_kind():
    dev = _Dev(kind="TPU v5e", platform="cpu")
    assert chip_peak_flops(dev) == 0.0
    assert chip_peak_hbm_bw(dev) == 0.0


def test_resolve_serving_peaks_env_override(monkeypatch):
    monkeypatch.setenv("PT_ROOFLINE_KIND", "TPU v5e")
    pf, pb = resolve_serving_peaks(_Dev(kind="cpu", platform="cpu"))
    assert pf == pytest.approx(PEAK_BF16["TPU v5e"])
    assert pb == pytest.approx(PEAK_HBM_BPS["TPU v5e"])
    monkeypatch.delenv("PT_ROOFLINE_KIND")
    pf, pb = resolve_serving_peaks(_Dev(kind="cpu", platform="cpu"))
    assert (pf, pb) == (0.0, 0.0)


# -------------------------------------------------------- geometry & models
def _llama8b():
    """Llama-3-8B-ish GQA geometry."""
    return ModelGeometry(num_layers=32, hidden=4096, intermediate=14336,
                         vocab=128256, heads=32, kv_heads=8, head_dim=128)


def test_geometry_from_config_duck_types_llama():
    from paddle_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(num_hidden_layers=8, vocab_size=512,
                           hidden_size=128, intermediate_size=256,
                           num_attention_heads=8, num_key_value_heads=4)
    g = ModelGeometry.from_config(cfg)
    assert (g.num_layers, g.hidden, g.vocab) == (8, 128, 512)
    assert (g.heads, g.kv_heads, g.head_dim) == (8, 4, 16)
    assert g.num_experts == 0
    assert g.activated_params == g.resident_params   # dense: no experts


def test_moe_geometry_activated_vs_resident():
    dense = _llama8b()
    moe = ModelGeometry(num_layers=32, hidden=4096, intermediate=14336,
                        vocab=128256, heads=32, kv_heads=8, head_dim=128,
                        num_experts=8, experts_per_tok=2)
    # one token activates 2 expert MLPs but a batched forward streams 8
    assert moe.activated_params < moe.resident_params
    per_expert = moe.mlp_params_per_expert
    assert moe.resident_params - moe.activated_params == \
        32 * (8 - 2) * per_expert
    # a dense model of the same shape activates exactly one MLP per layer
    assert dense.activated_params == \
        moe.activated_params - 32 * 1 * per_expert


def test_gqa_shrinks_kv_bytes_by_head_grouping():
    gqa = _llama8b()
    mha = ModelGeometry(num_layers=32, hidden=4096, intermediate=14336,
                        vocab=128256, heads=32, kv_heads=32, head_dim=128)
    assert kv_bytes_per_position(gqa) * (32 // 8) == \
        pytest.approx(kv_bytes_per_position(mha))
    assert kv_bytes_per_position(gqa) == 32 * 2 * 8 * 128 * 2


def test_weight_bytes_counts_all_resident_experts():
    g = _llama8b()
    assert weight_bytes(g) == g.resident_params * 2


def test_phase_models_hand_check():
    g = ModelGeometry(num_layers=2, hidden=8, intermediate=16, vocab=32,
                      heads=2, kv_heads=1, head_dim=4)
    # one decode token against 10 cached positions
    fl = phase_flops(g, tokens=1, kv_read_positions=10)
    assert fl == 2 * g.activated_params + 4 * 2 * 4 * 10
    by = phase_bytes(g, tokens=1, weight_passes=1, kv_read_positions=10)
    assert by == (weight_bytes(g) + 10 * kv_bytes_per_position(g)
                  + 1 * kv_bytes_per_position(g) + 32 * 4)


def test_decode_is_bandwidth_bound_prefill_chunk_compute_bound():
    """The roofline story the ledger exists to tell: a batch-32 decode
    tick at 1k context sits far left of every chip's balance point
    (bandwidth-bound), while a 1k-token causal prefill chunk clears the
    v5p balance (compute-bound) — and decode intensity is decades below
    prefill intensity."""
    g = _llama8b()
    d_fl = phase_flops(g, tokens=32, kv_read_positions=32 * 1024)
    d_by = phase_bytes(g, tokens=32, weight_passes=1,
                       kv_read_positions=32 * 1024)
    d_ai = arith_intensity(d_fl, d_by)
    pairs = 1024 * 1025 // 2
    p_fl = phase_flops(g, tokens=1024, kv_read_positions=pairs)
    p_by = phase_bytes(g, tokens=1024, weight_passes=1,
                       kv_read_positions=pairs)
    p_ai = arith_intensity(p_fl, p_by)
    assert d_ai * 5 < p_ai
    for chip in PEAK_HBM_BPS:
        assert roofline_verdict(d_ai, PEAK_BF16[chip],
                                PEAK_HBM_BPS[chip]) == "bandwidth-bound"
    assert roofline_verdict(p_ai, PEAK_BF16["TPU v5p"],
                            PEAK_HBM_BPS["TPU v5p"]) == "compute-bound"
    assert roofline_verdict(p_ai, 0.0, 0.0) == "undefined"


# ----------------------------------------------------------- choke point
def test_record_serving_throughput_sets_gauges_and_report():
    g = _llama8b()
    rep = record_serving_throughput(
        "decode", seconds=2.0, tokens=64, weight_passes=2,
        kv_read_positions=64 * 512, geom=g,
        peak_flops=PEAK_BF16["TPU v5e"],
        peak_hbm_bps=PEAK_HBM_BPS["TPU v5e"])
    assert rep["bound"] == "bandwidth-bound"
    assert rep["mfu"] > 0 and rep["mbu"] > 0
    assert rep["mbu"] == pytest.approx(rep["bytes"] / 2.0 / 819e9)
    assert METRICS.get("serving_mbu").value(phase="decode") == \
        pytest.approx(rep["mbu"])
    assert METRICS.get("serving_mfu").value(phase="decode") == \
        pytest.approx(rep["mfu"])
    assert METRICS.get("serving_arith_intensity").value(phase="decode") == \
        pytest.approx(rep["arith_intensity"])
    doc = serving_roofline_report()
    assert doc["machine"]["balance_flops_per_byte"] == \
        pytest.approx(PEAK_BF16["TPU v5e"] / PEAK_HBM_BPS["TPU v5e"])
    assert doc["phases"]["decode"]["tokens"] == 64


def test_record_serving_throughput_unknown_peaks_exports_zero_not_fake():
    g = _llama8b()
    rep = record_serving_throughput(
        "decode", seconds=1.0, tokens=8, weight_passes=1,
        kv_read_positions=8 * 64, geom=g)
    assert rep["mfu"] == 0.0 and rep["mbu"] == 0.0
    assert rep["bound"] == "undefined"
    assert rep["arith_intensity"] > 0          # the intensity stays real
    assert METRICS.get("serving_mbu").value(phase="decode") == 0.0


def test_record_serving_throughput_skips_empty_windows():
    g = _llama8b()
    assert record_serving_throughput("decode", seconds=0.0, tokens=5,
                                     weight_passes=1, kv_read_positions=1,
                                     geom=g) == {}
    assert record_serving_throughput("decode", seconds=1.0, tokens=0,
                                     weight_passes=0, kv_read_positions=0,
                                     geom=g) == {}
    assert serving_roofline_report()["phases"] == {}


# --------------------------------------------------------- engine anatomy
_BREAKDOWN_PHASES = ("prefill", "draft", "verify", "sample", "host")


def _bench_shaped_engine(**kw):
    """A Llama-shaped serving config, eight layers deep — the
    acceptance criterion measures THIS engine."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(num_hidden_layers=8, vocab_size=512,
                           hidden_size=128, intermediate_size=256,
                           num_attention_heads=8, num_key_value_heads=4,
                           max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    from paddle_tpu.serving import LLMEngine
    return LLMEngine(model, num_slots=4, block_size=8, max_prompt_len=32,
                     max_seq_len=96, **kw)


def _tiny_spec_engine():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    model = LlamaForCausalLM(cfg)
    dcfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=32,
                            num_attention_heads=4, num_key_value_heads=2,
                            vocab_size=64)
    draft = LlamaForCausalLM(dcfg)
    from paddle_tpu.serving import LLMEngine
    return LLMEngine(model, draft_model=draft, spec_k=3, num_slots=4,
                     block_size=8, max_prompt_len=16, max_seq_len=64)


def _sums():
    hist = METRICS.get("serving_tick_breakdown_seconds")
    tick = METRICS.get("serving_tick_seconds")
    parts = {p: hist.value(phase=p) for p in _BREAKDOWN_PHASES}
    return parts, tick.value()


def test_tick_breakdown_reconciles_tick_for_tick():
    """After EVERY tick, each breakdown phase has observed exactly as
    many samples as ``serving_tick_seconds`` and the per-tick phase
    sums add up to the tick total — reconciliation by construction,
    checked per tick, not just in aggregate."""
    from paddle_tpu.serving import Request
    eng = _tiny_spec_engine()
    rs = np.random.RandomState(0)
    for l in (4, 7, 11, 5, 9):
        eng.add_request(Request(rs.randint(0, 64, (l,)),
                                max_new_tokens=8))
    ticks = 0
    while eng.has_work():
        eng.step()
        ticks += 1
        parts, tick = _sums()
        assert tick["count"] == ticks
        for p in _BREAKDOWN_PHASES:
            assert parts[p]["count"] == ticks, \
                f"phase {p} missed a tick ({parts[p]['count']} vs {ticks})"
        total = sum(parts[p]["sum"] for p in _BREAKDOWN_PHASES)
        assert math.isclose(total, tick["sum"], rel_tol=1e-9), \
            f"tick {ticks}: breakdown sum {total} != tick sum {tick['sum']}"
    assert ticks > 2
    eng.assert_quiescent()
    # the spec engine exercised every device phase at least once
    hist = METRICS.get("serving_tick_breakdown_seconds")
    for p in ("prefill", "draft", "verify"):
        assert hist.value(phase=p)["sum"] > 0.0


def test_tick_breakdown_reconciles_at_async_depth():
    """ISSUE 20: the five-phase reconciliation must hold tick-for-tick
    at ``async_depth>0`` too — device-overlapped drain/emit work folds
    into the "sample" slice, only exposed host time lands in "host",
    and every tick observes ``serving_tick_host_hidden_seconds`` exactly
    once, so the hidden column reconciles against the tick count."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import LLMEngine, Request
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    eng = LLMEngine(LlamaForCausalLM(cfg), num_slots=4, block_size=8,
                    max_prompt_len=16, max_seq_len=64, async_depth=2)
    rs = np.random.RandomState(0)
    for l in (4, 7, 11, 5):
        eng.add_request(Request(rs.randint(0, 64, (l,)), max_new_tokens=8))
    hid = METRICS.get("serving_tick_host_hidden_seconds")
    ticks = 0
    while eng.has_work():
        eng.step()
        ticks += 1
        parts, tick = _sums()
        assert tick["count"] == ticks
        for p in _BREAKDOWN_PHASES:
            assert parts[p]["count"] == ticks, \
                f"phase {p} missed a tick ({parts[p]['count']} vs {ticks})"
        total = sum(parts[p]["sum"] for p in _BREAKDOWN_PHASES)
        assert math.isclose(total, tick["sum"], rel_tol=1e-9), \
            f"tick {ticks}: breakdown sum {total} != tick sum {tick['sum']}"
        assert hid.value()["count"] == ticks
    eng.assert_quiescent()
    assert ticks > 2
    doc = serving_roofline_report()
    anat = doc["tick_anatomy"]
    assert anat["ticks_seconds"] == pytest.approx(_sums()[1]["sum"])
    assert anat["host_hidden_seconds"] == pytest.approx(hid.value()["sum"])
    assert anat["host_exposed_seconds"] == \
        pytest.approx(_sums()[0]["host"]["sum"])
    assert 0.0 <= anat["overlap_fraction"] <= 1.0


def test_bench_shaped_engine_exports_bandwidth_bound_decode_mbu(monkeypatch):
    """The acceptance criterion: under PT_ROOFLINE_KIND="TPU v5e" the
    bench-shaped engine run exports a nonzero ``serving_mbu{decode}``
    with a bandwidth-bound verdict (the v5e arithmetic exercised on
    CPU), and the whole per-phase report hangs together."""
    monkeypatch.setenv("PT_ROOFLINE_KIND", "TPU v5e")
    from paddle_tpu.serving import Request
    eng = _bench_shaped_engine()
    rs = np.random.RandomState(7)
    for l in (12, 20, 8, 16):
        eng.add_request(Request(rs.randint(0, 512, (l,)),
                                max_new_tokens=12))
    out = eng.run()
    assert len(out) == 4
    mbu = METRICS.get("serving_mbu").value(phase="decode")
    mfu = METRICS.get("serving_mfu").value(phase="decode")
    assert 0.0 < mbu < 1.0       # CPU is far below a v5e HBM roof
    assert 0.0 < mfu < 1.0
    doc = serving_roofline_report()
    dec = doc["phases"]["decode"]
    assert dec["bound"] == "bandwidth-bound"
    assert dec["mbu"] == pytest.approx(mbu)
    assert dec["tokens"] > 0 and dec["seconds"] > 0
    assert doc["phases"]["prefill"]["arith_intensity"] > \
        dec["arith_intensity"]
    assert doc["machine"]["peak_hbm_bps"] == pytest.approx(819e9)


def test_cpu_engine_exports_zero_mbu_not_fabricated(monkeypatch):
    """Without the env override a CPU run must export 0.0 (undefined)
    for MFU/MBU — never a number derived from an assumed chip — while
    the intensity gauge stays real."""
    monkeypatch.delenv("PT_ROOFLINE_KIND", raising=False)
    from paddle_tpu.serving import Request
    eng = _tiny_spec_engine()
    rs = np.random.RandomState(3)
    for l in (5, 9, 6):
        eng.add_request(Request(rs.randint(0, 64, (l,)),
                                max_new_tokens=6))
    eng.run()
    assert METRICS.get("serving_mbu").value(phase="decode") == 0.0
    assert METRICS.get("serving_mfu").value(phase="decode") == 0.0
    assert METRICS.get("serving_arith_intensity").value(phase="decode") > 0
    for ph, rep in serving_roofline_report()["phases"].items():
        assert rep["bound"] == "undefined", ph
