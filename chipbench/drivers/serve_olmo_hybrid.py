"""The serving driver for the Olmo-Hybrid family: ``drivers/serve.py``'s run
with the verdict from ``chipbench/correct_olmo_hybrid.py``, and a warm-up
that leaves what a deployment that has served the mix's documents before
holds: their K/V in the prefix cache AND a snapshot of the recurrent state
at the end of each.

``_drive``, ``_warm_up`` and ``PadCounter`` are ``drivers/serve.py``'s own,
imported unchanged: the clock, the arrivals, the window, the stamps and the
record the readers are given are the accepted ones. After the window, and
before the engine is freed, the recurrent states of the slots the window's
end cut off are read from the cache (``_live_slots``): ``correct`` compares
them with the reference's recurrence (``correct_olmo_hybrid``'s
``state_gap``). The reference differs
because ``correct.served`` imports the LLaMA-shaped one by name (PERF.md,
section 7 (b): once a configuration names those modules, this file goes).
The warm-up adds two waves behind ``_warm_up``'s: the engine snapshots a
prefix when a K/V match shows it a second time, so each document is asked
again (its snapshot is taken, and the program that takes one is compiled),
and then once more (a snapshot is restored, and that program compiled): no
program is left to compile inside the window.
"""
import gc
import importlib

import numpy as np

from chipbench import correct_olmo_hybrid
from chipbench.drivers import peak_bytes
from chipbench.drivers.serve import _drive, _warm_up, clock


def _warm_up_state(engine, seed, vocab, block, documents):
    from paddle_tpu.serving.types import Request
    rng = np.random.default_rng([int(seed), 0xA12])
    for _ in range(2):
        for doc in documents:
            own = rng.integers(1, vocab, 2 * block, dtype=np.int32)
            engine.add_request(Request(np.concatenate([doc, own]),
                                       max_new_tokens=2))
        engine.run()
    engine.pop_finished()


def _live_slots(engine, reqs, requests):
    """{index into the traffic: slot} of the window's requests that still
    sit in a decoding slot when it ends, each with its state as the
    programs left it: after the prompt and every served token but the last
    (which no tick has read yet)."""
    live = {}
    for slot in np.nonzero(engine.active)[0]:
        r = engine.requests[int(engine.slot_req[slot])]
        for q in requests:
            if (q["comparable"] and not q["finished"]
                    and q["tokens"] == list(r.tokens)
                    and np.array_equal(reqs[q["index"]]["prompt"], r.prompt)
                    and engine.cur[slot] == q["prompt_len"]
                    + len(q["tokens"]) - 1):
                live[q["index"]] = int(slot)
    return live


def run(cell, cfg, mix, seed, seconds, trace_dir, t_process_start, note,
        compiles):
    """-> the run's record (see ``drivers/serve.py: _drive``)."""
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
    documents = [system[k] for k in sorted(system)]
    _warm_up(engine, seed, cfg["vocab_size"], opts["max_prompt_len"],
             opts["block_size"], documents)
    _warm_up_state(engine, seed, cfg["vocab_size"], opts["block_size"],
                   documents)
    stats = engine.mgr.cache_stats
    note(phase="warm_up_done", setup_so_far_s=clock() - t_process_start,
         memory_peak_bytes=peak_bytes(devs),
         state_snapshots={k[5:]: v for k, v in stats.items()
                          if k.startswith("snap_")})
    record = _drive(engine, reqs, cell, mix["params"]["rate"] == "backlog",
                    seconds, trace_dir, t_process_start, compiles)
    record["memory_peak_bytes"] = peak_bytes(devs)
    note(phase="window_done", requests=len(record["requests"]),
         refused=record.pop("refused")[:3], ticks=len(record["ticks"]),
         generator_lateness_s=record.pop("lateness"),
         memory_peak_bytes=record["memory_peak_bytes"],
         cache=record["cache"])

    # ---- correct: the states of the slots the window's end cut off are
    # read, the engine's memory is freed, then the reference runs
    live = _live_slots(engine, reqs, record["requests"])
    chosen = correct_olmo_hybrid.choose(record["requests"], seed,
                                        cell["check"], live)
    rows = [(reqs[q["index"]]["prompt"], q["tokens"]) for q in chosen]
    states = {k: (len(rows[k][0]) + len(rows[k][1]) - 1,
                  [np.asarray(s[live[q["index"]]])
                   for s, _ in engine.exe.cache.states])
              for k, q in enumerate(chosen) if q["index"] in live}
    del engine, model
    gc.collect()
    note(phase="engine_freed", bytes_in_use=(
        devs[0].memory_stats() or {}).get("bytes_in_use"))
    verdict = correct_olmo_hybrid.served(cfg, seed, rows, cell["check"],
                                         states)
    note(phase="correct", **verdict)
    record.update(cell=cell, config=cfg, correct=verdict["correct"])
    return record
