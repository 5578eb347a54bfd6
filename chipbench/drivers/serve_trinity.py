"""The serving driver for the Trinity family: ``drivers/serve.py``'s run
with the verdict from ``chipbench/correct_trinity.py``.

``_drive``, ``_warm_up`` and ``PadCounter`` are ``drivers/serve.py``'s own,
imported unchanged: the clock, the arrivals, the window, the stamps and the
record the readers are given are the accepted ones. The verdict differs
because ``correct.served`` imports the LLaMA-shaped reference by name
(PERF.md, section 7 (b): once a configuration names those modules, this
file goes). The traffic shares no prefix and the engine keeps no prefix
cache, so the warm-up is handed no system prompts. After the window the
engine (and its two spaces' pools) and the model are dropped, then the
reference runs.
"""
import gc
import importlib

from chipbench import correct_trinity
from chipbench.drivers import peak_bytes
from chipbench.drivers.serve import _drive, _warm_up, clock


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
    _warm_up(engine, seed, cfg["vocab_size"], opts["max_prompt_len"],
             opts["block_size"], [])
    # the start-up heap (the requests' arrays, the engine, JAX's caches)
    # is set aside as a deployment sets it aside once it is up: a full
    # pass of Python's collector over it is ~0.1 s with the chip idle, one
    # to four of them a 20 s window, which was most of this cell's spread
    # (PERF.md section 6, PR 44)
    gc.collect()
    gc.freeze()
    note(phase="warm_up_done", setup_so_far_s=clock() - t_process_start,
         memory_peak_bytes=peak_bytes(devs), bytes_in_use=(
             devs[0].memory_stats() or {}).get("bytes_in_use"))
    record = _drive(engine, reqs, cell, mix["params"]["rate"] == "backlog",
                    seconds, trace_dir, t_process_start, compiles)
    record["memory_peak_bytes"] = peak_bytes(devs)
    note(phase="window_done", requests=len(record["requests"]),
         refused=record.pop("refused")[:3], ticks=len(record["ticks"]),
         generator_lateness_s=record.pop("lateness"),
         memory_peak_bytes=record["memory_peak_bytes"],
         cache=record["cache"])

    # ---- correct: the engine's pools and the model are freed, then the
    # reference runs
    chosen = correct_trinity.choose(record["requests"], seed, cell["check"])
    rows = [(reqs[q["index"]]["prompt"], q["tokens"]) for q in chosen]
    del engine, model
    gc.unfreeze()
    gc.collect()
    note(phase="engine_freed", bytes_in_use=(
        devs[0].memory_stats() or {}).get("bytes_in_use"))
    t_ref = clock()
    verdict = correct_trinity.served(cfg, seed, rows, cell["check"])
    note(phase="correct", reference_s=clock() - t_ref, **verdict)
    record.update(cell=cell, config=cfg, correct=verdict["correct"])
    return record
