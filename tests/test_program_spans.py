"""The program's own names in the trace (ISSUE 25): one span mechanism
that reaches both the tracer's buffer and the profiler's host plane, the
spans of the serving tick and of the train step's call, the pad counter
at the executor's entries, and a name on every Pallas kernel.

All CPU, none timing-sensitive: times are only compared with each other.
"""
import ast
import gc
import glob
import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import GOODPUT, METRICS, TRACER, span
from paddle_tpu.serving import LLMEngine, Request, engine as engine_mod
from paddle_tpu.serving import executor as executor_mod

PALLAS_DIR = Path(pt.__file__).parent / "ops" / "pallas"
KERNEL_NAMES = {
    "paged_decode_attention", "paged_chunk_attention", "flash_attention_fwd",
    "flash_attention_dq", "flash_attention_dkv", "rms_norm_fwd", "fused_rope",
    "grouped_matmul", "grouped_matmul_dx", "grouped_matmul_dw",
    "gated_delta_chunk", "paged_latent_decode_attention",
    "paged_latent_chunk_attention"}


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=32,
                           num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=64)
    return LlamaForCausalLM(cfg)


def _engine(model, **kw):
    opts = dict(num_slots=3, block_size=4, max_prompt_len=8, max_seq_len=48,
                eos_token_id=None)
    opts.update(kw)
    return LLMEngine(model, **opts)


def _traffic(eng, seed=0):
    """Two short prompts (one padded admission forward) and one of 19
    tokens (three chunk forwards), six tokens each."""
    rs = np.random.RandomState(seed)
    for n in (5, 19, 3):
        eng.add_request(Request(rs.randint(0, 64, (n,)), max_new_tokens=6))


def _run(eng):
    ticks = 0
    while eng.has_work():
        eng.step()
        ticks += 1
    return ticks


def _spans(collector=False):
    """The buffer's spans. A collection may start anywhere, so a test
    that counts spans leaves the collector's (``host.gc``) out."""
    return [e for e in TRACER.export()["traceEvents"] if e["ph"] == "X"
            and (collector or e["name"] != "host.gc")]


def _descendants(evs, root):
    kids = {}
    for e in evs:
        kids.setdefault(e["parent"], []).append(e)
    out, todo = [], [root["id"]]
    while todo:
        for e in kids.get(todo.pop(), ()):
            out.append(e)
            todo.append(e["id"])
    return out


class PadCounter:
    """The benchmark's rule (``chipbench/drivers/serve.py``): token-rows
    sent to the two prefill entries, and how many carried a prompt token."""

    def __init__(self, exe):
        self.rows = self.useful = self.calls = 0
        for name in ("prefill", "prefill_chunk"):
            setattr(exe, name, self._wrap(getattr(exe, name)))

    def _wrap(self, fn):
        def counted(ids, lens, *a, **kw):
            self.calls += 1
            self.rows += int(np.size(ids))
            self.useful += int(np.sum(lens))
            return fn(ids, lens, *a, **kw)
        return counted


# ------------------------------------------------------------- the span

def test_buffer_clock_is_perf_counters_clock():
    """``time.monotonic_ns`` (the buffer's clock) and ``time.perf_counter``
    (the benchmark's) read one clock on Linux, so their stamps interleave."""
    mono = time.get_clock_info("monotonic")
    perf = time.get_clock_info("perf_counter")
    assert mono.implementation == perf.implementation
    a = time.perf_counter()
    m = time.monotonic_ns() * 1e-9
    b = time.perf_counter()
    assert a - 1e-6 <= m <= b + 1e-6


def test_span_off_records_nothing_and_reads_no_clock(monkeypatch):
    reads = []
    real = time.monotonic_ns
    monkeypatch.setattr(time, "monotonic_ns",
                        lambda: reads.append(1) or real())
    gc.disable()                 # the collector's two reads are its own
    try:
        with span("ghost", n=1) as sp:
            assert not sp.recording
            sp.set(more=2)
    finally:
        gc.enable()
    assert reads == [] and TRACER.export()["traceEvents"] == []


def test_span_ids_parents_cat_and_set():
    TRACER.enable()
    with span("outer") as outer:
        with span("wait", cat="device_wait", rows=4) as inner:
            inner.set(useful=3)
        with span("second"):
            pass
        assert outer.recording
    with span("alone"):
        pass
    by = {e["name"]: e for e in _spans()}
    assert by["outer"]["parent"] is None and by["alone"]["parent"] is None
    assert by["wait"]["parent"] == by["second"]["parent"] == by["outer"]["id"]
    assert len({e["id"] for e in by.values()}) == 4
    assert by["wait"]["cat"] == "device_wait" and by["outer"]["cat"] == "host"
    assert by["wait"]["args"] == {"rows": 4, "useful": 3}
    assert "args" not in by["outer"]


def test_span_takes_the_callers_clock_reads():
    """``begin(t)``/``end(t)``: a caller's accounting and the span share
    one read per edge, so the two agree to the nanosecond."""
    TRACER.enable()
    t0 = time.monotonic_ns()
    sp = span("slice").begin(t0)
    t1 = time.monotonic_ns()
    sp.end(t1)
    (ev,) = _spans()
    assert ev["ts"] == t0 / 1e3 and ev["dur"] == (t1 - t0) / 1e3


def test_decorated_span_nests_under_the_callers_span():
    @span("callee")
    def f():
        return 1

    TRACER.enable()
    with span("caller"):
        f()
    f()
    evs = _spans()
    caller = next(e for e in evs if e["name"] == "caller")
    callees = [e for e in evs if e["name"] == "callee"]
    assert [e["parent"] for e in callees] == [caller["id"], None]


# ------------------------------------------------ the collector's pauses

def _gc_seconds(generation=2):
    return METRICS.get("python_gc_seconds_total").value(
        generation=str(generation))


@pytest.mark.parametrize("recording", [True, False])
def test_a_collection_is_a_host_gc_span_and_always_moves_the_counter(
        recording):
    """Recording: ``host.gc`` under the span that was open, with the
    generation and what was collected. Everything off: nothing in the
    buffer. Either way the pause is in ``python_gc_seconds_total``."""
    before = _gc_seconds()
    if recording:
        TRACER.enable()
    with span("outer"):
        cycle = []
        cycle.append(cycle)
        del cycle
        gc.collect()
    TRACER.disable()
    assert _gc_seconds() > before
    if not recording:
        assert TRACER.export()["traceEvents"] == []
        return
    evs = _spans(collector=True)
    outer = next(e for e in evs if e["name"] == "outer")
    full = [e for e in evs if e["name"] == "host.gc"
            and e["args"]["generation"] == 2]
    assert len(full) == 1 and full[0]["parent"] == outer["id"]
    assert full[0]["args"]["collected"] >= 1
    assert outer["ts"] <= full[0]["ts"]
    assert full[0]["ts"] + full[0]["dur"] <= outer["ts"] + outer["dur"]
    # the counter holds the span's own two clock reads
    assert _gc_seconds() - before >= full[0]["dur"] * 1e-6 - 1e-9


def test_export_under_a_collection_does_not_deadlock():
    """``export`` allocates with the buffer's lock held; a collection
    that starts there ends in ``_emit`` on the same thread."""
    TRACER.enable()
    for _ in range(200):
        with span("filler"):
            pass
    real = TRACER._lock
    calls = []

    class Collecting:
        def __enter__(self):
            if not real.acquire(timeout=5):      # not re-entrant: no span
                raise RuntimeError("the buffer's lock is held")
            if not calls:
                calls.append(1)
                gc.collect()     # a pass while this thread holds the lock
            return self

        def __exit__(self, *exc):
            real.release()
            return False

    TRACER._lock = Collecting()
    try:
        names = [e["name"] for e in TRACER.export()["traceEvents"]]
    finally:
        TRACER._lock = real
        TRACER.disable()
    assert names.count("filler") == 200 and "host.gc" in names


# ------------------------------------------------------ the serving tick

def test_engine_run_with_everything_off_leaves_the_buffer_empty(model):
    eng = _engine(model)
    _traffic(eng)
    _run(eng)
    assert TRACER.export()["traceEvents"] == []


def test_every_tick_is_one_step_span_with_its_children_inside(model):
    eng = _engine(model)
    _traffic(eng)
    TRACER.enable()
    ticks = _run(eng)
    TRACER.disable()
    evs = _spans()
    ids = {e["id"] for e in evs}
    assert len(ids) == len(evs)
    assert all(e["parent"] is None or e["parent"] in ids for e in evs)
    steps = [e for e in evs if e["name"] == "serving.step"]
    assert len(steps) == ticks
    assert [e["args"]["tick"] for e in steps] == list(range(1, ticks + 1))
    assert all(e["parent"] is None for e in steps)
    tid = steps[0]["tid"]
    seen = set()
    for st in steps:
        inside = _descendants(evs, st)
        names = [e["name"] for e in inside]
        seen.update(names)
        assert names.count("serving.bookkeeping") == 1
        assert names.count("serving.expire") == 1
        for e in inside:
            assert e["tid"] == tid
            assert st["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= st["ts"] + st["dur"] + 1e-3
    # every span of the tick belongs to some tick
    in_ticks = {e["id"] for st in steps for e in _descendants(evs, st)}
    assert in_ticks | {s["id"] for s in steps} == ids
    assert seen >= {"serving.expire", "serving.admit", "serving.prefill",
                    "exe.prefill", "exe.prefill_chunk", "exe.sample",
                    "serving.decode", "exe.decode_tick", "serving.fetch",
                    "serving.emit", "serving.bookkeeping"}
    waits = {e["name"] for e in evs if e["cat"] == "device_wait"}
    assert waits == {"exe.sample", "serving.fetch"}
    by_id = {e["id"]: e for e in evs}
    for e in evs:
        if e["name"].startswith("exe.prefill"):
            assert by_id[e["parent"]]["name"] == "serving.prefill"
        if e["name"] in ("exe.decode_tick", "serving.fetch"):
            assert by_id[e["parent"]]["name"] == "serving.decode"
    # one emit span a decode tick; its tokens are the tick's tokens
    emits = [e for e in evs if e["name"] == "serving.emit"]
    decodes = [e for e in evs if e["name"] == "serving.decode"]
    assert len(emits) == len(decodes)
    assert ([e["args"]["tokens"] for e in emits]
            == [e["args"]["slots"] for e in decodes])
    admitted = sum(e["args"]["admitted"] for e in evs
                   if e["name"] == "serving.admit")
    assert admitted == 3
    total = sum(len(r.tokens) for r in eng.requests.values())
    first = 3                     # each request's first token comes from
    assert sum(e["args"]["tokens"] for e in emits) == total - first  # prefill


# ----------------------------------------- the dispatch edge and the waits

# the executor's module-level programs and the ``program`` each is sent as
PROGRAMS = {"_TICK_JIT": "tick", "_PREFILL_JIT": "prefill",
            "_PREFILL_CHUNK_JIT": "chunk", "_SAMPLE_ROWS_JIT": "sample",
            "_SPLIT_JIT": "split", "_PREFIX_COW_JIT": "cow",
            "_STATE_TAKE_JIT": "state_take",
            "_STATE_RESTORE_JIT": "state_restore"}


def _count_jitted_calls(monkeypatch):
    """Wrap every program the executor module holds -> {program: calls}."""
    calls = {}
    for attr, program in PROGRAMS.items():
        def counted(*a, _fn=getattr(executor_mod, attr), _p=program, **kw):
            calls[_p] = calls.get(_p, 0) + 1
            return _fn(*a, **kw)
        monkeypatch.setattr(executor_mod, attr, counted)
    return calls


def _hybrid_engine():
    from chipbench.builders import olmo_hybrid as builder
    cfg = json.loads((Path(pt.__file__).parents[1] / "chipbench" / "tests"
                      / "cells" / "configs"
                      / "tiny-olmo-hybrid.json").read_text())
    return LLMEngine(builder.build(cfg, 3).eval(), num_slots=4, block_size=4,
                     max_prompt_len=16, max_seq_len=128, num_blocks=64,
                     num_state_snapshots=4)


@pytest.mark.parametrize("family", ["llama", "hybrid"])
def test_every_jitted_call_is_one_dispatch_span(model, monkeypatch, family):
    """One ``exe.dispatch`` a jitted call, named by its ``program``;
    ``seq`` rises by one from call to call, whatever the program."""
    if family == "llama":
        eng, rounds = _engine(model), 2      # a second round: radix hits,
        prompts = [np.random.RandomState(1).randint(0, 64, (n,))  # one cow
                   for n in (5, 19, 3)]
        prompts.append(np.concatenate([prompts[1][:9], [7, 7]]))
        want = {"tick", "prefill", "chunk", "sample", "split", "cow"}
    else:
        eng, rounds = _hybrid_engine(), 3    # K/V, a snapshot, a restore
        doc = np.random.default_rng(7).integers(1, 256, 32, dtype=np.int32)
        prompts = [np.concatenate([doc, [9, 8, 7, 6, 5]])]
        want = {"tick", "chunk", "sample", "split", "state_take",
                "state_restore"}
    calls = _count_jitted_calls(monkeypatch)
    first = eng.exe.seq
    TRACER.enable()
    for _ in range(rounds):
        for p in prompts:
            eng.add_request(Request(p, max_new_tokens=4))
        _run(eng)
    TRACER.disable()
    sent = sorted((e for e in _spans() if e["name"] == "exe.dispatch"),
                  key=lambda e: e["ts"])
    assert all(e["cat"] == "dispatch" for e in sent)
    by_program = {}
    for e in sent:
        by_program[e["args"]["program"]] = by_program.get(
            e["args"]["program"], 0) + 1
    assert by_program == calls and set(calls) >= want
    assert [e["args"]["seq"] for e in sent] == list(
        range(first + 1, first + 1 + len(sent)))
    assert eng.exe.seq == first + len(sent)
    # the span is around the call and nothing else: no span below it. A
    # forward's one host array, the staged vector, goes up inside the edge
    assert not {e["parent"] for e in _spans()} & {e["id"] for e in sent}
    by_id = {e["id"]: e for e in _spans()}
    entries = {"tick": "exe.decode_tick", "prefill": "exe.prefill",
               "chunk": "exe.prefill_chunk"}
    for e in sent:
        if e["args"]["program"] in entries:
            assert by_id[e["parent"]]["name"] == entries[e["args"]["program"]]
            assert e["args"]["uploads"] == 1


@pytest.mark.parametrize("depth", [0, 2])
def test_every_device_wait_names_the_program_it_waited_for(model, depth):
    """``seq`` on a ``device_wait`` span is a dispatched program's. In the
    synchronous loop it is the newest one that is not the key's split, so
    nothing is in flight when the wait ends; at ``async_depth`` 2 the
    cruising ticks wait for an older one."""
    # blocks of 16: the pipeline cruises only while no slot's table grows
    eng = _engine(model, async_depth=depth, block_size=16 if depth else 4)
    _traffic(eng)
    TRACER.enable()
    _run(eng)
    TRACER.disable()
    evs = _spans()
    sent = {e["args"]["seq"]: e for e in evs if e["name"] == "exe.dispatch"}
    waits = [e for e in evs if e["cat"] == "device_wait"]
    assert len(waits) > 8
    behind = 0
    for w in waits:
        d = sent[w["args"]["seq"]]
        assert d["args"]["program"] == (
            "sample" if w["name"] == "exe.sample" else "tick")
        assert d["ts"] + d["dur"] <= w["ts"]
        newest = max(s for s, e in sent.items() if e["ts"] < w["ts"]
                     and e["args"]["program"] != "split")
        assert w["args"]["seq"] <= newest
        behind += w["args"]["seq"] < newest
    assert (behind == 0) if depth == 0 else (behind >= 3)


def _calls_outside(fn_node, covered):
    """Names of the calls in ``fn_node`` that stand in no ``with`` block
    whose context ``covered`` accepts (the context expressions themselves
    left out)."""
    out = []

    def walk(node, inside):
        if isinstance(node, ast.With):
            here = inside or any(covered(i.context_expr)
                                 for i in node.items)
            for item in node.items:
                if not covered(item.context_expr):
                    walk(item.context_expr, inside)
            for child in node.body:
                walk(child, here)
            return
        if isinstance(node, ast.Call) and not inside:
            f = node.func
            out.append(f.attr if isinstance(f, ast.Attribute) else f.id)
        for child in ast.iter_child_nodes(node):
            walk(child, inside)

    for stmt in fn_node.body:
        walk(stmt, False)
    return out


def _function(module, name):
    tree = ast.parse(Path(module.__file__).read_text())
    (fn,) = [n for n in ast.walk(tree)
             if isinstance(n, ast.FunctionDef) and n.name == name]
    return fn


def _direct_jit_calls(source):
    """``name:line`` of every call of a ``_*_JIT`` or ``_cp_*`` name."""
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Call):
            f = node.func
            name = (f.id if isinstance(f, ast.Name)
                    else f.attr if isinstance(f, ast.Attribute) else "")
            if re.fullmatch(r"_\w+_JIT|_cp_\w+", name):
                found.append(f"{name}:{node.lineno}")
    return found


def test_no_jitted_call_of_the_executor_stands_outside_the_helper():
    """Every ``_*_JIT(...)`` and ``self._cp_*(...)`` of
    ``serving/executor.py`` is called by ``_dispatch`` alone, which calls
    what it is handed."""
    assert _direct_jit_calls(Path(executor_mod.__file__).read_text()) == []
    assert "jitted" in [
        n.func.id for n in ast.walk(_function(executor_mod, "_dispatch"))
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)]
    # the rule sees both ways of writing such a call, and no other
    assert _direct_jit_calls(
        "x = _TICK_JIT(m)\ny = self._cp_tick(m)\nself._no_cp_lora(l)\n"
        "self._dispatch('tick', _TICK_JIT, m)") == ["_TICK_JIT:1",
                                                    "_cp_tick:2"]


def _asarray_calls(fn_node):
    """Lines of ``fn_node``'s calls of ``jnp.asarray`` / ``jax.device_put``:
    each a dispatch of its own, made before the edge opens."""
    return [n.lineno for n in ast.walk(fn_node) if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr in ("asarray", "device_put")
            and isinstance(n.func.value, ast.Name)
            and n.func.value.id in ("jnp", "jax")]


@pytest.mark.parametrize("entry", ["decode_tick", "prefill", "prefill_chunk"])
def test_a_forward_entry_uploads_nothing_before_its_edge(entry):
    """The three forwards every tick runs hand their staging arrays to
    ``_dispatch`` packed (``Staging.pack``, numpy alone): no upload of one
    stands in the entry, outside the edge with the device idle."""
    fn = _function(executor_mod, entry)
    assert _asarray_calls(fn) == []
    assert "pack" in [n.func.attr for n in ast.walk(fn)
                      if isinstance(n, ast.Call)
                      and isinstance(n.func, ast.Attribute)]
    # the rule sees an upload where there is one
    assert _asarray_calls(_function(executor_mod, "verify_chunk")) != []
    assert _asarray_calls(ast.parse(
        "def f(x):\n    a = np.asarray(x)\n    return jnp.asarray(a)")) == [3]


def test_stage_closes_the_decode_ticks_tree(model):
    """``serving.stage`` once a tick, between ``serving.prefill`` and
    ``serving.decode``; with it a decode tick's children follow one
    another in ``_step_inner``'s order and leave none of its calls
    uncovered but the ones listed here."""
    eng = _engine(model)
    _traffic(eng)
    TRACER.enable()
    _run(eng)
    TRACER.disable()
    evs = _spans()
    decoded = 0
    for st in (e for e in evs if e["name"] == "serving.step"):
        kids = sorted((e for e in evs if e["parent"] == st["id"]),
                      key=lambda e: e["ts"])
        names = [e["name"] for e in kids]
        assert names.count("serving.stage") == 1
        if "serving.decode" not in names:
            continue
        decoded += 1
        assert names == ["serving.expire", "serving.admit",
                         "serving.prefill", "serving.stage",
                         "serving.decode", "serving.emit",
                         "serving.bookkeeping"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        stage = kids[3]
        assert set(stage["args"]) == {"grown", "preempted"}
        assert 0 <= stage["args"]["grown"] <= eng.num_slots
    assert decoded > 5
    grown = sum(e["args"].get("grown", 0) for e in evs
                if e["name"] == "serving.stage")
    assert grown > 0             # 6 tokens a request cross a block of 4

    def spanned(ctx):
        return (isinstance(ctx, ast.Call) and (
            getattr(ctx.func, "id", None) == "_span"
            or getattr(ctx.func, "attr", None) == "_tick_timer"))
    outside = _calls_outside(_function(engine_mod, "_step_inner"), spanned)
    assert sorted(set(outside)) == sorted({
        # chaos hooks, no-ops without a rule installed
        "fault_point",
        # the pipelined loop's own branch (its spans: ``_async_step``)
        "_async_block_reason", "_async_step", "_drain_async",
        # beam groups: no cell runs one
        "list", "_beam_advance", "values", "asarray",
        # the accounting's clock reads and the cp histogram
        "perf_counter", "observe",
        # which slots ran, for the emit loop
        "nonzero"})


def test_submit_span_carries_the_requests_id(model):
    eng = _engine(model)
    TRACER.enable()
    rids = [eng.add_request(Request(np.arange(1, n), max_new_tokens=2))
            for n in (4, 6, 9)]
    with pytest.raises(ValueError):
        eng.add_request(Request(np.arange(1, 4), max_new_tokens=0))
    TRACER.disable()
    subs = [e for e in _spans() if e["name"] == "serving.submit"]
    assert [e["args"]["rid"] for e in subs[:3]] == rids
    assert "args" not in subs[3]             # refused: no id
    assert all(e["parent"] is None for e in subs)
    _run(eng)


def test_decode_span_counts_the_pool_blocks_the_kernel_walks(model):
    """``serving.decode`` carries ``kv_blocks``: ceil(len / block) summed
    over the slots the tick runs, len counting the token being written."""
    eng = _engine(model)
    _traffic(eng)
    walked = []
    real = eng.exe.decode_tick

    def counted(tokens, run_mask, *a, **kw):
        lens = eng.cur[np.asarray(run_mask, bool)] + 1
        walked.append((int(np.sum(run_mask)),
                       int(np.sum(-(-lens // eng.block_size)))))
        return real(tokens, run_mask, *a, **kw)

    eng.exe.decode_tick = counted
    TRACER.enable()
    _run(eng)
    TRACER.disable()
    decodes = [e for e in _spans() if e["name"] == "serving.decode"]
    assert len(decodes) == len(walked) > 5
    assert [(e["args"]["slots"], e["args"]["kv_blocks"])
            for e in decodes] == walked
    # ragged lengths: more than one block a slot, fewer than the table
    assert max(k for _, k in walked) > 3 * 2
    assert all(k <= s * eng.max_blocks_per_seq for s, k in walked)


@pytest.mark.parametrize("traffic", ["two_chunks", "radix_hit"])
def test_chunk_span_counts_the_pool_blocks_the_kernel_walks(model, traffic):
    """``exe.prefill_chunk`` carries ``kv_blocks``: ceil((offset + chunk
    length) / block) summed over the rows that carry a chunk, whatever
    else the padded batch holds."""
    eng = _engine(model)
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, 64, (13,))
    if traffic == "two_chunks":
        # 8 tokens at offset 0, then 5 at offset 8: blocks of 4
        eng.add_request(Request(prompt, max_new_tokens=2))
        want = [2, 4]
    else:
        # the second prompt shares nine tokens with the first: two cached
        # blocks and, copied on write, the first token of the third; one
        # chunk of 2 at offset 9
        eng.add_request(Request(prompt, max_new_tokens=2))
        _run(eng)
        eng.add_request(Request(np.concatenate([prompt[:9], [7, 7]]),
                                max_new_tokens=2))
        want = [3]
    walked = []
    real = eng.exe.prefill_chunk

    def counted(ids, lens, offs, *a, **kw):
        lens, offs = np.asarray(lens), np.asarray(offs)
        walked.append(int(sum(-(-(o + n) // eng.block_size)
                              for o, n in zip(offs, lens) if n)))
        return real(ids, lens, offs, *a, **kw)

    eng.exe.prefill_chunk = counted
    TRACER.enable()
    _run(eng)
    TRACER.disable()
    chunks = [e for e in _spans() if e["name"] == "exe.prefill_chunk"]
    assert [e["args"]["kv_blocks"] for e in chunks] == walked == want
    assert all(e["args"]["rows"] == 3 * 8 for e in chunks)
    if traffic == "radix_hit":
        assert eng.mgr.cache_stats["token_hits"] == 9


@pytest.mark.parametrize("depth", [0, 2])
def test_span_pad_counts_equal_the_wrappers_exactly(model, depth):
    eng = _engine(model, async_depth=depth)
    pads = PadCounter(eng.exe)
    _traffic(eng, seed=depth)
    TRACER.enable()
    _run(eng)
    TRACER.disable()
    sent = [e for e in _spans() if e["name"].startswith("exe.prefill")]
    assert len(sent) == pads.calls > 2
    assert sum(e["args"]["rows"] for e in sent) == pads.rows
    assert sum(e["args"]["useful"] for e in sent) == pads.useful == 27
    assert {e["name"] for e in sent} == {"exe.prefill", "exe.prefill_chunk"}


def test_async_ticks_fetch_and_emit_under_the_tick(model):
    eng = _engine(model, async_depth=2)
    _traffic(eng)
    TRACER.enable()
    ticks = _run(eng)
    TRACER.disable()
    evs = _spans()
    steps = [e for e in evs if e["name"] == "serving.step"]
    assert len(steps) == ticks
    step_ids = {e["id"] for e in steps}
    fetches = [e for e in evs if e["name"] == "serving.fetch"]
    assert fetches and all(e["cat"] == "device_wait" for e in fetches)
    # a drained tick's fetch hangs off the tick itself, a synchronous
    # tick's off its decode slice; both lie inside some tick
    inside = {e["id"] for st in steps for e in _descendants(evs, st)}
    assert {e["id"] for e in fetches} <= inside
    assert any(e["parent"] in step_ids for e in fetches)
    total = sum(len(r.tokens) for r in eng.requests.values())
    emitted = sum(e["args"]["tokens"] for e in evs
                  if e["name"] == "serving.emit")
    assert emitted == total - 3


def test_pad_rows_waste_counts_the_admission_forward_too(model):
    """``_prefill``'s whole unused rows count as ``_prefill_chunks``' do."""
    eng = _engine(model)          # 3 slots x 8 tokens a padded forward
    eng.add_request(Request(np.arange(1, 6), max_new_tokens=2))
    eng.step()
    assert GOODPUT.waste_by_why().get("pad_rows") == (3 - 1) * 8
    rs = np.random.RandomState(4)
    eng.add_request(Request(rs.randint(0, 64, (19,)), max_new_tokens=2))
    _run(eng)
    # three chunk forwards of one row each on top of the admission's
    assert GOODPUT.waste_by_why()["pad_rows"] == (3 - 1) * 8 * 4


def test_profiler_alone_turns_the_spans_on_and_holds_them_by_name(
        model, tmp_path):
    """No tracer, no option: ``jax.profiler.start_trace`` is the switch.
    The spans land in the buffer and in the trace's host plane."""
    from jax.profiler import ProfileData
    eng = _engine(model)
    _traffic(eng)
    eng.step()                               # compiled before the trace
    assert not TRACER.enabled and _spans() == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        ticks = _run(eng)
    finally:
        jax.profiler.stop_trace()
    steps = [e for e in _spans() if e["name"] == "serving.step"]
    assert len(steps) == ticks > 3
    eng.add_request(Request(np.arange(1, 5), max_new_tokens=2))
    _run(eng)                                # the profile is over:
    assert len(_spans()) == len(TRACER.export()["traceEvents"])
    assert sum(e["name"] == "serving.step" for e in _spans()) == ticks
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    host = [p for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:CPU")]
    names = [ev.name for p in host for line in p.lines for ev in line.events]
    assert names.count("serving.step") == ticks
    for name in ("serving.decode", "exe.decode_tick", "serving.fetch",
                 "exe.prefill_chunk", "serving.bookkeeping"):
        assert name in names, name


# ------------------------------------------------------- the train step

def test_instrumented_jit_call_is_a_span_with_its_signature_pass():
    from paddle_tpu.observability.compile import instrumented_jit
    step = instrumented_jit(lambda s, x: (s + x, (s * x).sum()),
                            name="train.step")
    s, x = jnp.ones(4), jnp.ones(4)
    step(s, x)                               # compiles; nothing recorded
    assert _spans() == []
    TRACER.enable()
    for _ in range(3):
        step(s, x)
    TRACER.disable()
    evs = _spans()
    calls = [e for e in evs if e["name"] == "train.step"]
    sigs = [e for e in evs if e["name"] == "jit.signature"]
    assert len(calls) == len(sigs) == 3 and len(evs) == 6
    assert [e["parent"] for e in sigs] == [e["id"] for e in calls]


# ------------------------------------------------------ the kernel names

def _pallas_calls():
    for path in sorted(PALLAS_DIR.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                yield f"{path.name}:{node.lineno}", node


def test_every_pallas_call_site_passes_a_name():
    sites = dict(_pallas_calls())
    assert len(sites) == 12
    names = []
    for where, call in sites.items():
        kw = {k.arg: k.value for k in call.keywords}
        assert "name" in kw, f"{where}: pallas_call without name="
        v = kw["name"]
        if isinstance(v, ast.Constant):
            names.append(v.value)
        else:                    # the one site two kernels reach: its
            assert isinstance(v, ast.Name), where    # callers name it
    assert len(set(names)) == len(names) == 11
    assert set(names) <= KERNEL_NAMES


def _tpu_lowering(fn, args, wrt):
    """``fn`` lowered for a TPU from this CPU (Mosaic kernels and all),
    as text with locations: a kernel's ``name`` is a scope of its call.
    Called inside a scope, as every kernel is in a model (autodiff wraps
    the outermost scope of a backward kernel: ``transpose(jvp(part))``);
    with ``wrt``, forward and backward of its sum."""
    def part(*a):
        with jax.named_scope("part"):
            out = fn(*a)
        return out.astype(jnp.float32).sum() if wrt else out
    if wrt:
        part = jax.value_and_grad(part, wrt)
    return jax.jit(part).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def _kernel_cases():
    """(the names expected, a function, its arguments, what to
    differentiate by or None)."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    from paddle_tpu.ops.pallas import norms, paged_attention as pa, rope
    f32, bf16 = jnp.float32, jnp.bfloat16
    S = jax.ShapeDtypeStruct
    pool = S((16, 16, 2, 128), bf16)
    tables, lens = S((2, 4), jnp.int32), S((2,), jnp.int32)
    qkv = S((1, 256, 2, 128), bf16)
    return [
        (("paged_decode_attention",),
         lambda q, k, v, t, l: pa.paged_decode_attention_pallas(
             q, k, v, t, l, interpret=False),
         (S((2, 4, 128), bf16), pool, pool, tables, lens), None),
        (("paged_chunk_attention",),
         lambda q, k, v, t, o, c: pa.paged_chunk_attention_pallas(
             q, k, v, t, o, c, interpret=False),
         (S((2, 16, 4, 128), bf16), pool, pool, tables, lens, lens), None),
        (("rms_norm_fwd",),
         lambda x, w: norms.rms_norm(x, w, 1e-5, interpret=False),
         (S((16, 256), bf16), S((256,), bf16)), None),
        (("fused_rope",),
         lambda x, c, s: rope.fused_rope(x, c, s, interpret=False),
         (S((1, 8, 4, 128), bf16), S((8, 64), f32), S((8, 64), f32)), None),
        (("flash_attention_fwd", "flash_attention_dq",
          "flash_attention_dkv"),
         lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                            interpret=False),
         (qkv, qkv, qkv), (0, 1, 2)),
        (("grouped_matmul", "grouped_matmul_dx", "grouped_matmul_dw"),
         lambda x, w, g: gmm.grouped_matmul(x, w, g, interpret=False,
                                            impl="pallas"),
         (S((256, 128), bf16), S((2, 128, 128), bf16), S((2,), jnp.int32)),
         (0, 1)),
    ]


@pytest.mark.parametrize("case", range(6))
def test_kernel_names_reach_the_tpu_lowering(case):
    """What the device trace prints for a Mosaic custom call is the last
    scope of its ``op_name``: the kernel's ``name``, else the enclosing
    scope or jitted function. Every kernel's call must carry its own."""
    names, fn, args, wrt = _kernel_cases()[case]
    text = _tpu_lowering(fn, args, wrt)
    # a kernel under a jit of its own (the chunk kernel) is lowered in a
    # function whose scopes start anew: its name may open the location
    scopes = re.findall(r'loc\("(?:[^"]*?/)?([^/"]+)/pallas_call"', text)
    assert "tpu_custom_call" in text
    assert set(scopes) == set(names), scopes


def test_grouped_plan_leaves_a_breadcrumb_and_a_count_a_traced_site():
    """The forward grouped product chooses its tiles when it is traced,
    and says so there: ``grouped_matmul:bm<..>:bn<..>`` among the
    kernels' breadcrumbs and ``moe_grouped_matmul_plans_total`` under the
    same two labels, once a traced call site, not once a call."""
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    from paddle_tpu.ops.pallas import paged_attention as pa
    plans = METRICS.get("moe_grouped_matmul_plans_total")
    assert plans.labelnames == ("block_m", "block_n")
    x = jnp.ones((16, 32), jnp.float32)
    w = jnp.ones((8, 32, 384), jnp.float32)
    g = jnp.full((8,), 2, jnp.int32)
    one = dict(block_m="8", block_n="128")
    two = dict(block_m="8", block_n="32")
    before = plans.value(**one), plans.value(**two)
    pa._trace_events.clear()
    fn = jax.jit(lambda a, b, c: gmm.grouped_matmul(a, b, c, impl="pallas"))
    fn(x, w, g), fn(x, w, g)               # the second from the jit's cache
    assert pa._trace_events == ["grouped_matmul:bm8:bn128"]
    assert plans.value(**one) == before[0] + 1
    # an expert MLP is two sites; the XLA twin and the dense path are none
    pa._trace_events.clear()
    down = jnp.ones((8, 192, 32), jnp.float32)
    act = gmm.grouped_matmul(x, w, g, impl="pallas")[:, :192]
    gmm.grouped_matmul(act, down, g, impl="pallas")
    gmm.grouped_matmul(x, w, g, impl="xla")
    gmm.grouped_matmul(x, w, g, impl="dense")
    assert pa._trace_events == ["grouped_matmul:bm8:bn128",
                                "grouped_matmul:bm8:bn32"]
    assert (plans.value(**one), plans.value(**two)) == (
        before[0] + 2, before[1] + 1)


def test_model_parts_carry_their_scopes(model):
    """attention / mlp / norm / lm_head / sampler on the serving tick."""
    from paddle_tpu.models.paged import PagedKVCache, llama_decode_tick
    cfg = model.cfg
    cache = PagedKVCache.init(cfg.num_hidden_layers, 8, 4,
                              cfg.num_key_value_heads, 8, 2, 4, cfg.dtype)
    i32 = jnp.int32
    text = jax.jit(llama_decode_tick, static_argnums=(10, 11)).trace(
        model, jnp.zeros(2, i32), cache, jnp.ones(2, bool),
        jnp.full(1, 2, i32), jnp.zeros(1, i32), jnp.zeros(1, i32),
        jax.random.PRNGKey(0), jnp.zeros(2), jnp.ones(2), None, False
    ).lower().as_text(debug_info=True)
    for scope in ("attention", "mlp", "norm", "lm_head", "sampler"):
        assert re.search(rf'loc\("jit\(llama_decode_tick\)/{scope}/', text), \
            scope
    train = jax.jit(lambda m, i: m.loss(i, i)).trace(
        model, jnp.zeros((1, 8), i32)).lower().as_text(debug_info=True)
    for scope in ("attention", "mlp", "norm", "lm_head"):
        assert f"/{scope}/" in train, scope


# ------------------------------------- two block spaces (window beside full)
@pytest.fixture(scope="module")
def window_model():
    import paddle_tpu as pt
    from paddle_tpu.models.trinity import TrinityConfig, TrinityForCausalLM
    pt.seed(0)
    return TrinityForCausalLM(TrinityConfig.tiny()).eval()


def test_two_space_spans_count_each_spaces_blocks_and_the_routing(
        window_model):
    """``serving.decode`` and ``exe.prefill_chunk`` of a model with window
    layers beside full ones carry ``kv_blocks_full`` (as ``kv_blocks``),
    ``kv_blocks_window`` (the blocks from the window's first on) and what
    the expert layers routed; ``serving.gauges`` each space's held and
    free blocks. Window 32, block 8, chunks of 24."""
    eng = LLMEngine(window_model, num_slots=2, block_size=8,
                    max_prompt_len=24, max_seq_len=128,
                    prefix_caching=False)
    rs = np.random.RandomState(3)
    eng.add_request(Request(rs.randint(1, 256, (70,)), max_new_tokens=6))
    eng.add_request(Request(rs.randint(1, 256, (10,)), max_new_tokens=6))
    TRACER.enable()
    _run(eng)
    TRACER.disable()
    evs = _spans()
    chunks = [e["args"] for e in evs if e["name"] == "exe.prefill_chunk"]
    # 70 tokens in chunks of 24 at offsets 0, 24, 48: ceil(end / 8) blocks
    # in the full layer, less those below offset - 32 + 1 in a window layer
    assert [(a["kv_blocks"], a["kv_blocks_full"], a["kv_blocks_window"],
             a["ctx_tokens"]) for a in chunks] == [
        (3, 3, 3, 24), (6, 6, 6, 48), (9, 9, 9 - 17 // 8, 70)]
    decodes = [e["args"] for e in evs if e["name"] == "serving.decode"]
    assert decodes and all(
        a["kv_blocks_full"] == a["kv_blocks"] >= a["kv_blocks_window"]
        for a in decodes)
    # the long row alone: len 75 -> 10 blocks, 5 below 75 - 32 = 43
    last = decodes[-1]
    assert (last["slots"], last["kv_blocks_full"],
            last["kv_blocks_window"]) == (1, 10, 5)
    # 4 pairs a token in each of the 4 expert layers; at most 16 experts
    assert all(a["routed_pairs"] == 16 * a["slots"] for a in decodes)
    assert all(0 < a["experts_hit"] <= 64 for a in decodes)
    routed = [e["args"] for e in evs if e["name"] == "exe.routed"]
    assert sorted(a["routed_pairs"] for a in routed) == sorted(
        16 * n for n in (10, 24, 24, 22))
    sweeps = [e["args"] for e in evs if e["name"] == "serving.gauges"]
    assert all({"full_held", "full_free", "window_held",
                "window_free"} <= set(a) for a in sweeps)
    assert max(a["full_held"] for a in sweeps) >= 10
    assert max(a["window_held"] for a in sweeps) <= (32 + 24) // 8 + 2 + 2
    assert sweeps[-1]["full_held"] == sweeps[-1]["window_held"] == 0


def test_a_model_of_one_kind_carries_no_space_counts(model):
    eng = _engine(model)
    _traffic(eng)
    TRACER.enable()
    _run(eng)
    TRACER.disable()
    for e in _spans():
        assert not {"kv_blocks_window", "kv_blocks_full", "window_held"} \
            & set(e.get("args", ())), e["name"]


def test_the_two_kinds_of_layer_are_two_scopes_in_the_lowering(window_model):
    """``attention.window`` and ``attention.full`` around the paged
    kernels' calls of a model with both kinds, in the tick and in the chunk
    program; a model of one kind has neither."""
    from paddle_tpu.models import paged
    cache = paged.PagedKVCache.init_for(window_model.cfg, 16, 8, 2, 6,
                                        window_blocks=12)
    i32, z2 = jnp.int32, jnp.zeros(2, jnp.int32)
    tick = jax.jit(paged.llama_decode_step_paged).trace(
        window_model, z2, cache, jnp.ones(2, bool)
    ).lower().as_text(debug_info=True)
    chunk = jax.jit(paged.llama_prefill_chunk_paged).trace(
        window_model, jnp.zeros((2, 8), i32), z2 + 8, z2, cache, z2,
        jnp.zeros((2, 6), i32), window_rows=jnp.zeros((2, 6), i32)
    ).lower().as_text(debug_info=True)
    for text in (tick, chunk):
        assert "/attention/attention.window/" in text
        assert "/attention/attention.full/" in text
