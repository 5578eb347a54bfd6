"""The serving driver: one ``LLMEngine`` on one chip, fed by a traffic mix.

Everything here is the benchmark's: the clock, the arrival schedule, the
token stamps (through ``Request.stream``), the tick stamps (around
``engine.step()``) and the count of padded token-rows (a wrapper around the
executor's two prefill entries). From the program it takes the engine, its
``stats`` and the radix manager's ``cache_stats``.

Timeline of a run: warm-up requests that reach every compiled program and
leave the mix's shared system prompts in the prefix cache, as a deployment
that has served them before holds them; then the traffic from its start; the
first ``lead_in_s`` seconds of traffic fill the slots and count as set-up;
the window is the ``seconds`` after that. Under ``rate: "backlog"`` the driver keeps
``backlog_waiting`` requests queued; otherwise a request is submitted when it
is due (open loop) and timed from then.
"""
import gc
import importlib
import time

import numpy as np

from chipbench import correct
from chipbench.drivers import peak_bytes

clock = time.perf_counter


class PadCounter:
    """Token-rows the two prefill programs were sent, and how many of them
    carried a prompt token, per call: [(time, ids.size, lens.sum()), ...]."""

    def __init__(self, exe):
        self.calls = []
        for name in ("prefill", "prefill_chunk"):
            setattr(exe, name, self._wrap(getattr(exe, name)))

    def _wrap(self, fn):
        def counted(ids, lens, *a, **kw):
            self.calls.append((clock(), int(np.size(ids)), int(np.sum(lens))))
            return fn(ids, lens, *a, **kw)
        return counted


def _warm_up(engine, seed, vocab, chunk, block, system_prompts):
    """Reach every program the window can reach, and only those, in as few
    ticks as that takes: plain prefill (a short prompt nobody shares),
    chunked prefill (a prompt just over a chunk), the decode tick and the
    sampler; then, once those are cached, the radix cache's copy-on-write
    (a prompt that parts from a cached one in the middle of a block). The
    mix's system prompts ride in the first wave's calls, each with a few
    tokens of its own behind it, and stay in the prefix cache."""
    from paddle_tpu.serving.types import Request
    rng = np.random.default_rng([int(seed), 0xA11])
    tok = lambda n: rng.integers(1, vocab, n, dtype=np.int32)
    common = tok(chunk - block // 2)
    held = [np.concatenate([p, tok(block)]) for p in system_prompts]
    for wave in ([tok(chunk // 2), np.concatenate([common, tok(block)])] + held,
                 [np.concatenate([common, tok(block + 1)])]):
        for p in wave:
            engine.add_request(Request(p, max_new_tokens=2))
        engine.run()
    engine.pop_finished()


def run(cell, cfg, mix, seed, seconds, trace_dir, t_process_start, note,
        compiles):
    """-> the run's record (see ``_drive``)."""
    import jax
    from paddle_tpu.serving import LLMEngine
    devs = jax.local_devices()[:cell["chips"]]

    builder = importlib.import_module(cfg["builder"])
    gen = importlib.import_module("chipbench.traffic." + mix["generator"])
    opts = dict(cell["engine"])
    model = builder.build(cfg, seed).eval()
    engine = LLMEngine(model, seed=int(seed) & 0x7FFFFFFF, **opts)
    reqs = gen.requests(seed, mix["params"], cfg["vocab_size"])
    system = {q["shared"]: q["prompt"][:mix["params"]["shared"]["tokens"]]
              for q in reqs if q["shared"] >= 0}
    _warm_up(engine, seed, cfg["vocab_size"], opts["max_prompt_len"],
             opts["block_size"], [system[k] for k in sorted(system)])
    note(phase="warm_up_done", setup_so_far_s=clock() - t_process_start,
         memory_peak_bytes=peak_bytes(devs))
    record = _drive(engine, reqs, cell, mix["params"]["rate"] == "backlog",
                    seconds, trace_dir, t_process_start, compiles)
    record["memory_peak_bytes"] = peak_bytes(devs)
    note(phase="window_done", requests=len(record["requests"]),
         refused=record.pop("refused")[:3], ticks=len(record["ticks"]),
         generator_lateness_s=record.pop("lateness"),
         memory_peak_bytes=record["memory_peak_bytes"])

    # ---- correct: the engine's state is freed, then the reference runs
    chosen = correct.choose(record["requests"], seed, cell["check"])
    rows = [(reqs[q["index"]]["prompt"], q["tokens"]) for q in chosen]
    del engine, model
    gc.collect()
    note(phase="engine_freed", bytes_in_use=(
        devs[0].memory_stats() or {}).get("bytes_in_use"))
    verdict = correct.served(cfg, seed, rows, cell["check"])
    note(phase="correct", **verdict)
    record.update(cell=cell, config=cfg, correct=verdict["correct"])
    return record


def _drive(engine, reqs, cell, backlog, seconds, trace_dir, t_process_start,
           compiles):
    """The traffic from its start to the end of the window (and, in an open
    loop, until the window's requests are finished or ``drain_cap_s`` has
    passed) -> the record the metric readers are given."""
    import jax
    from paddle_tpu.serving.types import Request

    pads = PadCounter(engine.exe)
    stamps, live, refused = {}, {}, []     # by index into reqs
    lateness = []

    def submit(i, now):
        r = Request(reqs[i]["prompt"],
                    max_new_tokens=reqs[i]["max_new_tokens"],
                    stream=lambda req, tok, i=i: stamps[i].append(clock()))
        stamps[i] = []
        try:
            engine.add_request(r)
        except Exception as e:            # refused: counted as failed
            refused.append((i, f"{type(e).__name__}: {e}"))
            return
        live[i] = {"req": r, "submitted": now}

    lead, drain_cap = float(cell["lead_in_s"]), float(cell["drain_cap_s"])
    keep = int(cell.get("backlog_waiting", 0))
    ticks, marks = [], {}
    t0 = clock()                            # the traffic starts
    w0, w1 = t0 + lead, t0 + lead + seconds
    tr0 = w1 - float(cell["trace_seconds"]) if trace_dir else None
    tracing, nxt = False, 0

    def snapshot(now):
        return {"t": now, "stats": dict(engine.stats),
                "cache": dict(engine.mgr.cache_stats),
                "compiles": compiles()}

    def sampled(i):
        """Is request i one of the window's? In an open loop, one due in it;
        under backlog, one that was served a token in it."""
        if backlog:
            return any(w0 <= t < w1 for t in stamps[i])
        return w0 <= t0 + reqs[i]["due"] < w1

    while True:
        now = clock()
        # the window opens and closes between two ticks, at the first
        # such instant after its nominal start and end: all the work and
        # all the time between the two marks are the window's
        if "w0" not in marks and now >= w0:
            marks["w0"] = snapshot(now)
            w0, w1 = now, now + seconds
        if tr0 is not None and not tracing and "trace_stop" not in marks \
                and now >= tr0:
            jax.profiler.start_trace(trace_dir)
            tracing, marks["trace_start"] = True, clock()
        if now >= w1:
            if "w1" not in marks:
                marks["w1"] = snapshot(now)
                w1 = now
                if tracing:
                    jax.profiler.stop_trace()
                    tracing, marks["trace_stop"] = False, clock()
            if backlog or now >= w1 + drain_cap or not any(
                    sampled(i) and not live[i]["req"].done for i in live):
                break
        if backlog:
            while nxt < len(reqs) and len(engine.queue) < keep:
                submit(nxt, now)
                nxt += 1
        else:
            while nxt < len(reqs) and t0 + reqs[nxt]["due"] <= now:
                lateness.append(now - (t0 + reqs[nxt]["due"]))
                submit(nxt, now)
                nxt += 1
        if engine.has_work():
            a = clock()
            engine.step()
            ticks.append((a, clock(), int(engine.active.sum()),
                          len(engine.prefilling)))
        elif nxt < len(reqs):
            time.sleep(max(0.0, min(0.002, t0 + reqs[nxt]["due"] - clock())))
        else:
            raise RuntimeError("the traffic mix ran out of requests: raise n")

    in_window = marks["w1"]["compiles"] - marks["w0"]["compiles"]
    if in_window:
        raise RuntimeError(f"{in_window} compile event(s) inside the window: "
                           "a shape was not warmed up")
    requests = []
    for i in sorted(i for i in live if sampled(i)):
        r = live[i]["req"]
        requests.append({
            "index": i, "due": (live[i]["submitted"] if backlog
                                else t0 + reqs[i]["due"]),
            "prompt_len": len(reqs[i]["prompt"]), "shared": reqs[i]["shared"],
            "asked": reqs[i]["max_new_tokens"], "stamps": stamps[i],
            "tokens": list(r.tokens),
            "finished": bool(r.done and r.finish_reason == "length"
                             and len(r.tokens) == reqs[i]["max_new_tokens"]),
            "comparable": bool(r.tokens) and (backlog or r.done),
            "reason": r.finish_reason})
    n_refused = sum(1 for i, _ in refused
                    if backlog or w0 <= t0 + reqs[i]["due"] < w1)
    # under backlog a request cut off by the end of the window has not
    # failed; the tokens it was served so far are compared like any other's
    unfinished = 0 if backlog else sum(not q["finished"] for q in requests)
    delta = lambda key, names: {
        k: marks["w1"][key].get(k, 0) - marks["w0"][key].get(k, 0)
        for k in names}
    return {
        "seconds": w1 - w0, "window": (w0, w1),
        "setup_s": w0 - t_process_start, "requests": requests,
        "tokens_in_window": sum(1 for s in stamps.values()
                                for t in s if w0 <= t < w1),
        "ticks": [t for t in ticks if t[0] >= w0 and t[1] <= w1],
        "stats": delta("stats", ("host_s", "device_s", "ticks")),
        "cache": delta("cache", marks["w1"]["cache"]),
        "prefill_calls": [c for c in pads.calls if w0 <= c[0] < w1],
        "num_slots": engine.num_slots,
        "attempted": len(requests) + n_refused,
        "failed": n_refused + unfinished, "refused": refused,
        "lateness": {"max": max(lateness, default=0.0),
                     "mean": float(np.mean(lateness)) if lateness else 0.0},
        "trace_span": (marks.get("trace_start"), marks.get("trace_stop")),
    }
