"""Observability subsystem (ISSUE 2): registry semantics, histogram
bucketing, export golden-formats, span nesting/Chrome-trace validity,
disabled-mode no-ops — plus the acceptance runs: a serving chaos run and
a trainer run, each dumping metrics (JSON + Prometheus) and a valid
Chrome trace with the fault-injection / preemption / NaN-skip events
visible."""
import json
import threading

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu import observability as obs
from paddle_tpu.observability import METRICS, TRACER, dump, span, instant
from paddle_tpu.observability.flops import (PEAK_BF16, chip_peak_flops, mfu,
                                            record_throughput)
from paddle_tpu.observability.metrics import MetricsRegistry


# ------------------------------------------------------------- registry

def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_get_or_create_same_instrument():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    b = reg.counter("x_total")
    assert a is b
    # conflicting re-registration (different kind or labels) raises
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("site",))


def test_labels_and_prebound():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", labelnames=("site",))
    c.inc(site="a")
    c.inc(2, site="b")
    bound = c.labels(site="a")
    bound.inc(3)
    assert c.value(site="a") == 4
    assert c.value(site="b") == 2
    with pytest.raises(ValueError):
        c.inc(wrong="a")            # undeclared label
    with pytest.raises(ValueError):
        c.inc()                     # missing declared label


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value() == 13


def test_histogram_bucket_boundaries_le_inclusive():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    snap = h.value()
    # le is INCLUSIVE (Prometheus): 0.1 falls in the 0.1 bucket
    assert snap["buckets"] == {"0.1": 2, "1": 4, "10": 5, "+Inf": 6}
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(106.65)


def test_histogram_rejects_bad_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("bad2", buckets=())


# -------------------------------------------------------------- exports

def _tiny_registry():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests served", labelnames=("code",)) \
       .inc(3, code="200")
    reg.gauge("depth", "queue depth").set(2)
    reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)) \
       .observe(0.05)
    return reg


def test_json_export_one_line_golden():
    reg = _tiny_registry()
    line = reg.to_json()
    assert "\n" not in line
    assert json.loads(line) == {
        "counters": {'reqs_total{code="200"}': 3},
        "gauges": {"depth": 2},
        "histograms": {"lat_seconds": {
            "buckets": {"0.1": 1, "1": 1, "+Inf": 1},
            "sum": 0.05, "count": 1}},
    }


def test_prometheus_export_golden():
    text = _tiny_registry().to_prometheus()
    assert text == (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 2\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 1\n'
        'lat_seconds_bucket{le="+Inf"} 1\n'
        "lat_seconds_sum 0.05\n"
        "lat_seconds_count 1\n"
        "# HELP reqs_total requests served\n"
        "# TYPE reqs_total counter\n"
        'reqs_total{code="200"} 3\n'
    )


def test_disabled_registry_is_noop():
    reg = _tiny_registry()
    before = reg.to_json()
    reg.disable()
    reg.counter("reqs_total", labelnames=("code",)).inc(99, code="200")
    reg.gauge("depth").set(999)
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(9.9)
    assert reg.to_json() == before      # export still works, frozen
    reg.enable()
    reg.gauge("depth").set(7)
    assert reg.get("depth").value() == 7


# -------------------------------------------------------------- tracing

def _no_gc(events):
    """A collection may start anywhere while spans record; its span
    (``host.gc``) is not what these tests count."""
    return [e for e in events if e["name"] != "host.gc"]


def test_span_nesting_and_chrome_trace_validity():
    TRACER.enable()
    with span("outer", step=1):
        with span("inner"):
            pass
        instant("marker", kind="test")
    doc = json.loads(TRACER.export_chrome_trace())
    evs = _no_gc(doc["traceEvents"])
    assert doc["displayTimeUnit"] == "ms"
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner", "marker"}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert by_name["marker"]["ph"] == "i"
    # nesting: inner's [ts, ts+dur) is contained in outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["tid"] == threading.get_ident()
    assert outer["args"] == {"step": 1}


def test_span_decorator_honors_later_enablement():
    @span("decorated")
    def f():
        return 42

    assert f() == 42                    # tracer off: no event, value intact
    assert TRACER.export()["traceEvents"] == []
    TRACER.enable()
    assert f() == 42
    assert [e["name"] for e in _no_gc(TRACER.export()["traceEvents"])] \
        == ["decorated"]


def test_disabled_tracer_records_nothing():
    with span("ghost"):
        instant("ghost-marker")
    assert TRACER.export()["traceEvents"] == []


def test_tracer_event_cap_counts_drops():
    from paddle_tpu.observability.tracing import Tracer
    t = Tracer(max_events=2)
    t.enable()
    for i in range(4):
        t.instant(f"e{i}")
    assert len(t.export()["traceEvents"]) == 2
    assert t.export()["otherData"]["dropped_events"] == 2


def test_dump_writes_three_artifacts(tmp_path):
    METRICS.counter("dump_probe_total").inc()
    TRACER.enable()
    with span("probe"):
        pass
    paths = dump(str(tmp_path / "snap"))
    blob = json.loads((tmp_path / "snap.metrics.json").read_text())
    assert blob["counters"]["dump_probe_total"] == 1
    assert "dump_probe_total 1" in (tmp_path / "snap.prom").read_text()
    trace = json.loads((tmp_path / "snap.trace.json").read_text())
    assert [e["name"] for e in _no_gc(trace["traceEvents"])] == ["probe"]
    assert set(paths) == {"json", "prom", "trace"}


# ------------------------------------------------------- FLOPs/MFU table

def test_flops_table_and_throughput_choke_point():
    assert chip_peak_flops(kind="TPU v5 lite") == PEAK_BF16["TPU v5 lite"]
    assert chip_peak_flops(kind="TPU v5p") == PEAK_BF16["TPU v5p"]
    assert chip_peak_flops(kind="cpu") == 0.0
    assert mfu(1000.0, 1e9, 0.0) == 0.0         # unknown peak → undefined
    got = record_throughput(1000.0, 1e9, 2e12)
    assert got == pytest.approx(0.5)
    snap = METRICS.snapshot()["gauges"]
    assert snap["train_tokens_per_sec"] == 1000.0
    assert snap["train_mfu"] == pytest.approx(0.5)


# --------------------------------------------------- acceptance: serving

@pytest.mark.chaos
def test_serving_chaos_run_dumps_full_telemetry(tmp_path):
    """A chaos-driven serve (induced preemptions + allocator faults)
    leaves a complete telemetry story: counters in JSON and Prometheus,
    latency histograms populated, and a valid Chrome trace whose
    timeline shows the engine ticks AND each injected fault."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import LLMEngine, Request
    from paddle_tpu.utils.faults import FAULTS

    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    model = LlamaForCausalLM(cfg)
    FAULTS.install("serving.preempt", every=4, times=4,
                   action=lambda ctx: ctx["engine"]._preempt())
    TRACER.enable()
    eng = LLMEngine(model, num_slots=2, block_size=4, max_prompt_len=16,
                    max_seq_len=32, preemption=True)
    rs = np.random.RandomState(0)
    for n in rs.randint(4, 10, 4):
        eng.add_request(Request(rs.randint(0, 64, (int(n),)),
                                max_new_tokens=6))
    ticks = 0
    while eng.has_work():
        eng.step()
        ticks += 1
        assert ticks < 200
    eng.assert_quiescent()
    paths = dump(str(tmp_path / "serve"))

    blob = json.loads(open(paths["json"]).read())
    ctr, hist = blob["counters"], blob["histograms"]
    assert ctr["serving_admissions_total"] >= 4
    assert ctr["serving_preemptions_total"] > 0
    assert ctr['faults_injected_total{site="serving.preempt"}'] > 0
    assert ctr["serving_tokens_total"] >= 4 * 6
    assert hist["serving_ttft_seconds"]["count"] >= 4
    assert hist["serving_tick_seconds"]["count"] == ticks

    prom = open(paths["prom"]).read()
    assert "# TYPE serving_preemptions_total counter" in prom
    assert 'serving_ttft_seconds_bucket{le="+Inf"}' in prom

    trace = json.loads(open(paths["trace"]).read())
    names = [e["name"] for e in trace["traceEvents"]]
    assert names.count("serving.step") == ticks
    assert "fault:serving.preempt" in names
    faults = [e for e in trace["traceEvents"]
              if e["name"] == "fault:serving.preempt"]
    assert all(e["ph"] == "i" for e in faults)


# --------------------------------------------------- acceptance: trainer

@pytest.mark.chaos
def test_trainer_chaos_run_dumps_full_telemetry(tmp_path):
    """A short training run with an injected NaN storm dumps telemetry
    showing the steps, the skips, and where each fault landed on the
    span timeline."""
    from paddle_tpu.train.trainer import Trainer, TrainerArgs
    from paddle_tpu.utils.faults import FAULTS

    pt.seed(0)
    m = nn.Linear(4, 1)
    tr = Trainer(m, opt.SGD(0.1),
                 lambda mod, x, y: nn.functional.mse_loss(mod(x), y),
                 TrainerArgs(max_steps=6, log_every=0, max_bad_steps=10))
    FAULTS.install("train.loss", on={1, 3}, action=lambda c: float("nan"))
    TRACER.enable()
    rs = np.random.RandomState(0)
    data = ((rs.randn(2, 4).astype(np.float32),
             rs.randn(2, 1).astype(np.float32)) for _ in range(6))
    state = tr.fit(data)
    assert int(state.step) == 6
    paths = dump(str(tmp_path / "train"))

    blob = json.loads(open(paths["json"]).read())
    ctr = blob["counters"]
    assert ctr["train_steps_total"] == 6
    assert ctr["train_nan_skips_total"] == 2
    assert ctr['faults_injected_total{site="train.loss"}'] == 2
    assert blob["histograms"]["train_step_seconds"]["count"] == 6
    assert blob["gauges"]["train_loss"] == pytest.approx(
        tr.history[-1]["loss"] if tr.history else blob["gauges"]["train_loss"])

    prom = open(paths["prom"]).read()
    assert "train_nan_skips_total 2" in prom
    assert "# TYPE train_step_seconds histogram" in prom

    trace = json.loads(open(paths["trace"]).read())
    names = [e["name"] for e in trace["traceEvents"]]
    assert names.count("train.step") == 6
    assert names.count("fault:train.loss") == 2
