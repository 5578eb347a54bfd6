"""The training driver: ``init_state`` + ``make_train_step`` over a
``HybridMesh``, fed seeded batches made on the host one step ahead.

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first ``check.steps`` steps (through the window's own
call and feed), reads from it what the reference is compared with, and hands
the same object to the window. A step ends when ``loss.block_until_ready()``
returns; the window opens and closes between two steps. After the window the
state is freed and the plain reference takes the same first steps.
"""
import gc
import importlib
import time

import numpy as np

from chipbench import correct_train, reference, weights
from chipbench.drivers import peak_bytes

clock = time.perf_counter


def run(cell, cfg, mix, seed, seconds, trace_dir, t_process_start, note,
        compiles):
    import jax
    import jax.numpy as jnp
    import paddle_tpu.optimizer as optim
    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.train import make_train_step
    from paddle_tpu.train.step import init_state

    builder = importlib.import_module(cfg["builder"])
    gen = importlib.import_module("chipbench.traffic." + mix["generator"])
    devs = jax.devices()[:cell["chips"]]
    mesh = HybridMesh(**cell["mesh"], devices=devs)
    o = cell["optimizer"]
    optimizer = optim.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["eps"], weight_decay=o["weight_decay"],
        grad_clip=optim.ClipGradByGlobalNorm(o["clip"]), multi_precision=True)
    check_steps = int(cell["check"]["steps"])
    tokens_per_step = mix["params"]["batch"] * mix["params"]["seq_len"]

    def feed(k):
        ids, labels = gen.batch(seed, k, mix["params"], cfg["vocab_size"])
        return (jax.device_put(ids, mesh.batch_sharding()),
                jax.device_put(labels, mesh.batch_sharding()))

    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))))
    dist = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    program = {"loss": [], "not_finite": 0}
    with mesh:
        model = builder.build(cfg, seed, **cell.get("model", {}))
        state = init_state(model, optimizer, mesh)
        del model
        step = make_train_step(lambda m, i, l: m.loss(i, l), optimizer, mesh)
        stamps = []                      # (start, end, loss) of every step
        nxt = feed(0)

        def one_step(k):
            """Step k through the window's own call and feed."""
            nonlocal state, nxt
            a = clock()
            state, loss = step(state, *nxt)
            nxt = feed(k + 1)            # made while the device works
            loss.block_until_ready()
            b = clock()
            loss = float(loss)
            program["not_finite"] += not np.isfinite(loss)
            stamps.append((a, b, loss))
            return loss

        def master(state):
            """The weights the optimizer updates: fp32 masters where held."""
            model = builder.leaves(state.model)
            held = state.opt_state.get("master")
            if held is None:
                return model
            return {k: model[k] if v is None else v
                    for k, v in builder.leaves(held).items()}

        for k in range(check_steps):
            program["loss"].append(one_step(k))
            if k == 0:               # the first gradient, as Adam's first
                program["grad_norm"] = {   # moment holds it after one step
                    leaf: float(norm(m)) / (1.0 - o["beta1"]) for leaf, m in
                    builder.leaves(state.opt_state["moment1"]).items()}
        now = master(state)
        first = dict(weights.make_top(seed, cfg))
        for i in range(cfg["num_hidden_layers"]):
            first.update({f"L{i}.{g}": w for g, w in
                          builder.program_layer(cfg, seed, i).items()})
        program["delta_norm"] = {
            leaf: float(dist(now[leaf], jax.device_put(first[leaf],
                                                       now[leaf].sharding)))
            for leaf in now}
        del now, first
        note(phase="first_steps_done", losses=program["loss"],
             setup_so_far_s=clock() - t_process_start,
             memory_peak_bytes=peak_bytes(devs))

        # ---- the window
        k, c0 = check_steps, compiles()
        w0 = clock()
        n0 = len(stamps)
        tr0 = w0 + seconds - float(cell["trace_seconds"]) if trace_dir else None
        tracing = False
        while clock() < w0 + seconds:
            if tr0 is not None and not tracing and clock() >= tr0:
                jax.profiler.start_trace(trace_dir)
                tracing = True
            one_step(k)
            k += 1
        w1 = clock()
        if tracing:
            jax.profiler.stop_trace()
        if compiles() != c0:
            raise RuntimeError(f"{compiles() - c0} compile event(s) inside "
                               "the window: a step compiled again")
    peak = peak_bytes(devs)
    window = stamps[n0:]
    note(phase="window_done", steps=len(window), memory_peak_bytes=peak)

    # ---- correct: the program's state is freed, then the reference trains
    del state, step, nxt
    gc.collect()
    note(phase="state_freed", bytes_in_use=[
        (d.memory_stats() or {}).get("bytes_in_use") for d in devs])
    t = clock()
    ref = reference.train(
        cfg, o, [gen.batch(seed, k, mix["params"], cfg["vocab_size"])
                 for k in range(check_steps)],
        weights.make_top(seed, cfg),
        lambda i: weights.make_layer(seed, i, cfg), devs)
    verdict = correct_train.trained(program, ref, builder.GROUPS,
                                    cell["check"]["limits"])
    note(phase="correct", reference_seconds=clock() - t, **verdict)
    return {
        "cell": cell, "config": cfg, "seconds": w1 - w0, "window": (w0, w1),
        "setup_s": w0 - t_process_start, "steps": window,
        "tokens_per_step": tokens_per_step, "chips": cell["chips"],
        "seq_len": mix["params"]["seq_len"],
        "device_kind": devs[0].device_kind,
        "attempted": len(window),
        "failed": sum(not np.isfinite(l) for _, _, l in window),
        "correct": verdict["correct"], "memory_peak_bytes": peak,
    }
