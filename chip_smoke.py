"""Prove that the two hot paths start on the chip, through the entry points
a user calls: ``LLMEngine`` serves a handful of requests and
``init_state`` + ``make_train_step`` take a few optimizer steps, both at
LLaMA-2-7B widths (hidden 4096, 32 heads of 128, MLP 11008, vocab 32000)
cut in depth only, with random weights made from ``--seed``.

    python chip_smoke.py              # one TPU chip; anything else fails
    python chip_smoke.py --chips 4    # ONLY the cross-chip legs (run by hand)
    timeout 240 python chip_smoke.py --latent   # ONLY the latent leg (by hand)
    python chip_smoke.py --grouped    # ONLY the grouped product's 8 shapes
    python chip_smoke.py --tiny       # CPU rehearsal of the control flow

One process, no children, no network, no git. Every phase fails the run on
any error. Earlier lines are one JSON object each; the last line of a
passing run on the chip is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
``--tiny`` never prints that line. Timings here are smoke timings, not a
benchmark.

Depths. One v5e chip has 16 GB. Serving at depth 8 holds 3.76 GB of bf16
weights and a 4.29 GB K/V pool (2048 blocks of 16 positions, 8 slots of
4096 positions): 8.06 GB of arguments and 0.55 GB of temporaries by
``memory_analysis()`` of the decode tick compiled for a described v5e.
Training at depth 2 holds 9.34 GB of state (bf16 parameters, fp32 master
weights and both Adam moments, 16 bytes a parameter) and 0.90 GB of
temporaries at batch 2 x 2048 under full remat. The run prints what
``memory_stats()`` says the chip really held.
"""
import argparse
import gc
import json
import os
import sys
import time

SERVE_DEPTH, TRAIN_DEPTH = 8, 2
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 4

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile = {"seconds": 0.0, "cache_hits": 0, "cache_misses": 0}


def say(**kw):
    print(json.dumps(kw), flush=True)


def check(ok, *why):
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError("chip_smoke check failed: "
                           + " ".join(str(w) for w in why))


def _listen():
    """Sum what JAX itself reports it spent tracing, lowering and compiling
    (or fetching from the persistent cache), and count cache hits."""
    import jax.monitoring as mon

    def on_duration(event, seconds, **_):
        if event in _COMPILE_EVENTS:
            _compile["seconds"] += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _compile["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _compile["cache_misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


class Phase:
    """Wall, compile and run seconds of one phase, printed when it ends."""

    def __init__(self, name):
        self.name, self.extra = name, {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), dict(_compile)
        return self.extra

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = _compile["seconds"] - self.c0["seconds"]
            say(phase=self.name, wall_s=round(wall, 3),
                compile_s=round(comp, 3), run_s=round(wall - comp, 3),
                cache_hits=_compile["cache_hits"] - self.c0["cache_hits"],
                cache_misses=(_compile["cache_misses"]
                              - self.c0["cache_misses"]),
                note="smoke timing, not a benchmark", **self.extra)


def mem(dev):
    st = dev.memory_stats() or {}
    return {k: st.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def free_device():
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------------ phases
def check_block_until_ready(tiny):
    """Does ``block_until_ready`` wait for the device? A chain of matmuls
    is timed three ways: dispatch only, until ``block_until_ready``
    returns, and a host fetch after that. If it waits, the fetch finds
    the work done and costs nothing next to the chain."""
    import jax
    import jax.numpy as jnp
    n, reps = (256, 8) if tiny else (4096, 400)
    w = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(x):
        for _ in range(reps // 8):
            x = x @ w
        return x

    x = jnp.ones((n, n), jnp.bfloat16)
    float(chain(x)[0, 0])                      # compile + warm
    t0 = time.perf_counter()
    y = x
    for _ in range(8):
        y = chain(y)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_block = time.perf_counter() - t0
    t1 = time.perf_counter()
    val = float(y[0, 0])
    t_fetch = time.perf_counter() - t1
    flops = 2.0 * n ** 3 * reps
    waits = t_fetch < 0.25 * t_block
    say(check="block_until_ready", matmuls=reps, n=n,
        dispatch_s=round(t_dispatch, 5), until_block_s=round(t_block, 5),
        fetch_after_block_s=round(t_fetch, 5),
        chain_tflops_smoke=round(flops / t_block / 1e12, 2),
        waits=waits, value=val)
    if not (tiny or waits):
        raise RuntimeError(
            "block_until_ready returned before the device finished: "
            f"the fetch after it took {t_fetch:.4f}s of {t_block:.4f}s")


def llama_cfg(tiny, depth, **kw):
    from paddle_tpu.models.llama import LlamaConfig
    if tiny:
        return LlamaConfig.tiny(num_hidden_layers=2,
                                max_position_embeddings=4096, **kw)
    return LlamaConfig.llama2_7b(num_hidden_layers=depth, **kw)


def serve(tiny, seed, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving import LLMEngine

    cfg = llama_cfg(tiny, SERVE_DEPTH)
    with Phase("serve") as out:
        pt.seed(seed)
        model = LlamaForCausalLM(cfg).eval()
        engine = LLMEngine(model, num_slots=8, block_size=16,
                           max_prompt_len=128, max_seq_len=4096, seed=seed)
        check(engine.prefix_caching and engine.async_depth == 0)
        rs = np.random.RandomState(seed)
        tok = lambda n: rs.randint(1, cfg.vocab_size, n).astype(np.int32)
        shared = tok(50)
        # (prompt, max_new_tokens): a prompt of 300 > max_prompt_len runs
        # chunked prefill; the second wave shares a 50-token prefix with a
        # request of the first and is served from the radix cache: three
        # full blocks shared, two tokens of a fourth copied on write
        wave1 = [(tok(300), 12), (tok(57), 24),
                 (np.concatenate([shared, tok(20)]), 16), (tok(5), 32),
                 (tok(128), 8)]
        wave2 = [(np.concatenate([shared, tok(13)]), 10),
                 (np.concatenate([shared, tok(150)]), 6)]
        pa._trace_events.clear()
        asked = {}
        for wave in (wave1, wave2):
            for prompt, n_new in wave:
                asked[engine.generate(prompt, max_new_tokens=n_new)] = (
                    prompt, n_new)
            engine.run()
        reqs = engine.requests
        for rid, (prompt, n_new) in asked.items():
            r = reqs[rid]
            check(r.done and len(r.tokens) == n_new,
                  f"request {rid}: done={r.done} reason={r.finish_reason} "
                  f"tokens={len(r.tokens)} asked={n_new}")
        engine.assert_quiescent()
        events = set(pa._trace_events)
        hits = engine.mgr.cache_stats.get("token_hits", 0)
        check(hits >= 2 * 48, f"radix cache served {hits} prompt tokens")

        # reference: ONE plain forward of the same model over every request
        # (prompt + generated tokens, teacher forcing keeps each position
        # comparable after a near-tie), rows padded on the right, which a
        # causal model never sees. That covers what each request went
        # through: chunked prefill, radix reuse with copy-on-write, plain
        # prefill and decode. A width that is no multiple of 128 keeps the
        # reference on XLA attention, independent of the flash kernel.
        rows = [np.concatenate([p, np.asarray(reqs[r].tokens[:-1], np.int32)])
                for r, (p, _) in asked.items()]
        width = -(-max(len(r) for r in rows) // 8) * 8
        width += 8 * (width % 128 == 0)
        ids = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            ids[i, :len(r)] = r
        logits = jax.jit(lambda m, i: m(i))(model, jnp.asarray(ids))
        check(logits.shape == ids.shape + (cfg.vocab_size,), logits.shape)
        # bf16 logits near 6 sit 2^-5 apart: the tolerance is 2^-5 of the
        # row's scale, five or six such steps (three were seen on the chip)
        rel = 2.0 ** -5 if cfg.dtype == jnp.bfloat16 else 1e-4
        checked, mismatches, worst, scale, by_request = 0, 0, 0.0, 0.0, []
        for i, (rid, (prompt, n_new)) in enumerate(asked.items()):
            # greedy tokens must be the reference's argmax, or lose to it
            # by no more than bf16 rounding of the logits
            got = np.asarray(reqs[rid].tokens, np.int32)
            lg = np.asarray(logits[i, len(prompt) - 1:len(prompt) - 1 + n_new],
                            np.float32)
            check(np.isfinite(lg).all(), f"request {rid}: logits not finite")
            want = lg.argmax(-1)
            at = np.arange(n_new)
            margin = lg[at, want] - lg[at, got]
            tol = rel * np.maximum(1.0, np.abs(lg).max(-1))
            check((margin <= tol).all(),
                  f"request {rid} (prompt {len(prompt)}): engine tokens "
                  f"{got.tolist()} vs reference argmax {want.tolist()}: "
                  f"logit margins {margin.tolist()} exceed {tol.tolist()}")
            checked += n_new
            mismatches += int((want != got).sum())
            worst = max(worst, float(margin.max()))
            scale = max(scale, float(np.abs(lg).max()))
            by_request.append([len(prompt), n_new, int((want != got).sum()),
                               float(margin.max())])
        check(mismatches <= checked // 4,
              f"{mismatches} of {checked} greedy tokens are not the "
              "reference's argmax: near-ties cannot explain that many")
        if not tiny:
            check({"chunk:pallas", "decode:pallas"} <= events
                  and not {"chunk:xla", "decode:xla"} & events, events)
        out.update(
            depth=cfg.num_hidden_layers, hidden=cfg.hidden_size,
            heads=cfg.num_attention_heads, vocab=cfg.vocab_size,
            requests=len(asked), prompt_tokens=int(sum(
                len(p) for p, _ in asked.values())),
            tokens_served=int(sum(n for _, n in asked.values())),
            ticks=engine.stats["ticks"], radix_token_hits=int(hits),
            reference_requests=len(rows), reference_positions=checked,
            reference_argmax_mismatches=mismatches,
            reference_max_margin=worst, reference_logit_scale=scale,
            reference_by_request_prompt_new_mismatches_margin=by_request,
            trace_events=sorted(events),
            kernel_downgrades=len({"chunk:xla", "decode:xla"} & events)
            if not tiny else None,
            memory=mem(dev))
    del engine, model, reqs
    free_device()


def make_train(tiny, seed, cfg, mesh=None):
    """(state, step, ids, labels) built as examples/train_llama.py does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.train import make_train_step
    from paddle_tpu.train.step import init_state

    pt.seed(seed)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-4, weight_decay=0.1,
                          grad_clip=opt.ClipGradByGlobalNorm(1.0),
                          multi_precision=True)
    state = init_state(model, optimizer, mesh)
    step = make_train_step(lambda m, i, l: m.loss(i, l), optimizer, mesh)
    batch, seq = (TRAIN_BATCH, 128) if tiny else (TRAIN_BATCH, TRAIN_SEQ)
    ids = jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq)))
    labels = jnp.concatenate(
        [ids[:, 1:], -100 * jnp.ones((batch, 1), ids.dtype)], axis=1)
    if mesh is not None:
        ids = jax.device_put(ids, mesh.batch_sharding())
        labels = jax.device_put(labels, mesh.batch_sharding())
    return state, step, ids, labels


def run_steps(state, step, ids, labels, n):
    """n optimizer steps on one repeated batch -> (state, losses, step_s).
    Only the first step may compile: a later one that does means the state
    the step returns is laid out differently from the state it was given."""
    import numpy as np
    losses, times = [], []
    for i in range(n):
        t0, c0 = time.perf_counter(), _compile["seconds"]
        state, loss = step(state, ids, labels)
        loss.block_until_ready()
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
        check(i == 0 or _compile["seconds"] == c0,
              f"step {i + 1} compiled again "
              f"({_compile['seconds'] - c0:.2f}s)")
    check(np.isfinite(losses).all(), losses)
    return state, losses, times


def train(tiny, seed, dev):
    cfg = llama_cfg(tiny, TRAIN_DEPTH)
    with Phase("train") as out:
        state, step, ids, labels = make_train(tiny, seed, cfg)
        compiled = step.lower(state, ids, labels).compile()
        kernels = compiled.as_text().count("tpu_custom_call")
        ma = compiled.memory_analysis()
        state, losses, times = run_steps(state, step, ids, labels,
                                         TRAIN_STEPS)
        check(losses[-1] < losses[0], losses)
        if not tiny:
            check(kernels > 0, "no Pallas kernel in the compiled train step")
        out.update(
            depth=cfg.num_hidden_layers, hidden=cfg.hidden_size,
            batch=list(ids.shape), steps=TRAIN_STEPS, losses=losses,
            tpu_custom_calls=kernels,
            step_s_smoke=[round(t, 4) for t in times],
            memory_analysis=None if ma is None else {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes},
            memory=mem(dev))
    del state, step, compiled
    free_device()


def multichip(tiny, seed, n):
    """The cross-chip legs and what they are compared with, nothing else:
    the one-device loss from the same seed, then the same train step on
    HybridMesh(fsdp=2, tp=2), then with ring attention over sp=4."""
    import jax
    import numpy as np

    from paddle_tpu.distributed import HybridMesh

    devs = jax.devices()[:n]
    steps = 2
    rtol = 2e-4 if tiny else 2e-2

    def kernel_path(text):
        """Mosaic kernels in a compiled step, and the path that means: under
        a mesh XLA partitions, dispatchers take the XLA formulations."""
        k = text.count("tpu_custom_call")
        return dict(tpu_custom_calls=k,
                    attention_and_norm_path="pallas" if k else "xla")

    with Phase("one_device_reference") as out:
        state, step, ids, labels = make_train(
            tiny, seed, llama_cfg(tiny, TRAIN_DEPTH))
        text = step.lower(state, ids, labels).compile().as_text()
        state, ref, _ = run_steps(state, step, ids, labels, steps)
        out.update(losses=ref, **kernel_path(text))
    del state, step
    free_device()

    def leg(name, mesh, cfg, expect):
        with Phase(name) as out, mesh:
            state, step, ids, labels = make_train(tiny, seed, cfg, mesh)
            text = step.lower(state, ids, labels).compile().as_text()
            found = sorted(c for c in (
                "all-gather", "all-reduce", "reduce-scatter",
                "collective-permute", "all-to-all") if c in text)
            check(set(expect) <= set(found), expect, found)
            leaves = [l for l in jax.tree_util.tree_leaves(state.model)
                      if hasattr(l, "sharding")]
            on = [len(l.sharding.device_set) for l in leaves]
            sharded = [l for l in leaves
                       if not l.sharding.is_fully_replicated]
            check(min(on) == n, f"a parameter sits on {min(on)} devices")
            check(len(ids.sharding.device_set) == n, ids.sharding)
            state, losses, times = run_steps(state, step, ids, labels, steps)
            np.testing.assert_allclose(losses, ref, rtol=rtol, err_msg=name)
            per_dev = [(d.memory_stats() or {}).get("bytes_in_use")
                       for d in devs]
            if not tiny:
                check(all(b and b > 2 ** 28 for b in per_dev), per_dev)
            out.update(losses=losses, reference=ref, collectives=found,
                       parameters=len(leaves), sharded_parameters=len(sharded),
                       bytes_in_use_per_device=per_dev,
                       step_s_smoke=[round(t, 4) for t in times],
                       **kernel_path(text))
        del state, step
        free_device()
        return len(sharded)

    sharded = leg("fsdp2_tp2", HybridMesh(fsdp=2, tp=2, devices=devs),
                  llama_cfg(tiny, TRAIN_DEPTH), ["all-reduce"])
    check(sharded > 0, "fsdp x tp sharded no parameter")
    ring_kv(tiny, seed, devs)
    leg("ring_sp4", HybridMesh(sp=n, devices=devs),
        llama_cfg(tiny, TRAIN_DEPTH, sequence_parallel="ring"),
        ["collective-permute"])


def ring_kv(tiny, seed, devs):
    """Ring attention on its own: q/k/v laid out over the sp axis must sit
    on every device, and the result must equal plain attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.distributed.ring_attention import make_ring_attention
    from paddle_tpu.ops.attention import xla_attention

    with Phase("ring_attention_kv") as out:
        mesh = HybridMesh(sp=len(devs), devices=devs)
        b, s, h, d = (1, 256, 2, 16) if tiny else (1, 2048, 32, 128)
        dt = jnp.float32 if tiny else jnp.bfloat16
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, d), dt) for kk in ks)
        want = np.asarray(xla_attention(q, k, v, is_causal=True), np.float32)
        lay = mesh.sharding(None, "sp", None, None)
        q, k, v = (jax.device_put(x, lay) for x in (q, k, v))
        with mesh:
            got = jax.jit(make_ring_attention(mesh, causal=True))(q, k, v)
        for x in (k, v, got):
            shards = x.addressable_shards
            check(len(x.sharding.device_set) == len(devs)
                  and len({sh.device for sh in shards}) == len(devs)
                  and shards[0].data.shape[1] == s // len(devs), x.sharding)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=1e-4 if tiny else 3e-2)
        out.update(kv_shape=[b, s, h, d], kv_devices=len(devs),
                   kv_shard_shape=list(k.addressable_shards[0].data.shape))
    free_device()


# -------------------------------------------------------------------- main
def latent(tiny, seed):
    """The shape that hung a chip in PR 41: a ``[1, 2048]`` prefill of a
    Kimi-K2 model at its published widths, the dense layer and two expert
    layers, through the latent chunk kernel, once as the tree serves it
    (``held_forward`` takes its passes under a ``lax.while_loop``) and once
    with the second size under a ``lax.cond``, two conditionals in one
    program. With the chunk kernel asking for 48 MiB of scoped VMEM the
    second form never returned; at the compiler's default both run and
    agree. Since PR 42 that kernel is the EXPANDED one (a prefill call's);
    its timings alone at the cell's shapes follow. A hung device cannot be
    timed out from inside: run this leg under ``timeout``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.distributed import moe
    from paddle_tpu.models import paged
    from paddle_tpu.models.kimi_k2 import KimiK2Config, KimiK2ForCausalLM

    t = 64 if tiny else 2048
    if tiny:
        cfg = KimiK2Config.tiny(num_hidden_layers=3, n_routed_experts=64,
                                held_experts=(0, 1), dtype=jnp.bfloat16)
    else:
        cfg = KimiK2Config(num_hidden_layers=3, vocab_size=20480,
                           held_experts=tuple(range(12)),
                           dtype=jnp.bfloat16)
    pt.seed(seed)
    model = KimiK2ForCausalLM(cfg).eval()
    blocks, width = t // 16 * 2, t // 16 * 2
    ids = np.zeros((1, t), np.int32)
    ids[0, :t // 2] = np.arange(t // 2) % 200 + 1
    rows = np.full((1, width), blocks, np.int32)
    rows[0, :t // 32] = np.arange(t // 32)

    def prefill():
        # a function of its own a form: a second trace of one function
        # would be answered from the first's
        return jax.jit(lambda model, cache: paged.llama_prefill_paged(
            model, jnp.asarray(ids), jnp.array([t // 2]), cache,
            jnp.array([0]), jnp.asarray(rows))[0])

    def under_a_conditional(xt, vals, idx, held, n_exp, gate_up, down,
                            live=None):
        """``held_forward`` with its common size or every pair under a
        ``lax.cond``: the form that hung."""
        n_tok, k = idx.shape
        n_held, n = gate_up.shape[0], n_tok * k
        lut = np.full((n_exp,), n_held, np.int32)
        lut[np.asarray(held)] = np.arange(n_held, dtype=np.int32)
        local = jnp.asarray(lut)[idx]
        if live is not None:
            local = jnp.where(live[:, None], local, n_held)
        flat, gate = local.reshape(n), vals.reshape(n)
        order = jnp.argsort(flat, stable=True)
        counts = jnp.sum(flat[:, None] == jnp.arange(n_held)[None, :],
                         axis=0, dtype=jnp.int32)
        pairs = jnp.sum(counts)

        def one(rows_):
            sel = order[:rows_]
            sizes = jnp.minimum(counts, jnp.maximum(
                rows_ - (jnp.cumsum(counts) - counts), 0))
            ys = moe.grouped_mlp_apply(xt[sel // k], gate_up, down, sizes)
            ys = jnp.where((jnp.arange(rows_) < pairs)[:, None],
                           ys.astype(jnp.float32), 0.0) * gate[sel][:, None]
            return jnp.zeros((n_tok, xt.shape[1]), jnp.float32).at[
                sel // k].add(ys, mode="drop")

        common = max(128, moe.held_rows(n, n_held, n_exp) // 2)
        y = jax.lax.cond(pairs <= common, lambda: one(common),
                         lambda: one(n))
        return y, pairs, jnp.sum((counts > 0).astype(jnp.int32))

    out = {}
    for form in ("while_loop", "cond"):
        held_forward = moe.held_forward
        if form == "cond":
            moe.held_forward = under_a_conditional
        try:
            cache = paged.PagedKVCache.init_for(cfg, blocks, 16, 4, width)
            fn = prefill().lower(model, cache).compile()
            text = fn.as_text()
            conds = text.count(" conditional(")
            say(latent=form, step="compiled", conditionals=conds,
                raised_scoped_regions=text.count('"scoped_memory_configs":[{'))
            # on the chip the second form must hold its two conditionals
            # (the CPU's compiler turns small ones into selects)
            check(tiny or conds == (2 if form == "cond" else 0),
                  "conditionals in the", form, "form:", conds)
            t0 = time.perf_counter()
            out[form] = np.asarray(fn(model, cache), np.float32)
            say(latent=form, step="ran",
                first_call_s=round(time.perf_counter() - t0, 4),
                finite=bool(np.isfinite(out[form]).all()))
        finally:
            moe.held_forward = held_forward
        check(np.isfinite(out[form]).all(), "latent prefill not finite", form)
    gap = float(np.abs(out["cond"] - out["while_loop"]).max())
    say(latent="both", widest_logit_difference=gap)
    check(gap < 0.05, "the two forms of held_forward differ by", gap)
    latent_chunk_kernel_alone(tiny, att=model.layers[0].self_attn)


def latent_chunk_kernel_alone(tiny, att):
    """The expanded chunk kernel by itself at the shapes of
    ``kimik2.serve.longshared`` (a ``[1, 2048]`` chunk of 64 heads over a
    pool ``[20480, 16, 640]`` behind a table 1,056 wide): ms a call, full
    and tail chunks at three offsets, and the kernel against its gather
    twin at a table the twin can hold."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import latent_attention as L

    c, n, width = (64, 64, 32) if tiny else (2048, 20480, 1056)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h, dt = att.num_heads, att.o_proj.dtype
    w_kvb = att.kv_b()
    q_nope = jax.random.normal(keys[0], (1, c, h, att.nope)).astype(dt)
    q_rope = jax.random.normal(keys[1], (1, c, h, att.rope_dim)).astype(dt)
    pool = jax.random.normal(keys[2], (n, 16, att.row_width)).astype(dt)
    pool = pool.at[:, :, att.rank + att.rope_dim:].set(0)
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(n)[None, :width], jnp.int32)
    fn = jax.jit(functools.partial(L.paged_latent_chunk_attention,
                                   scale=att.scale))

    def call(off, live):
        return fn(q_nope, q_rope, w_kvb, pool, tables,
                  jnp.array([off], jnp.int32), jnp.array([live], jnp.int32))

    small = (q_nope[:, :256], q_rope[:, :256], w_kvb, pool, tables[:, :32],
             jnp.array([200], jnp.int32), jnp.array([min(c, 251)], jnp.int32))
    twin = L.paged_latent_chunk_attention_xla(*small, scale=att.scale)
    got = L.paged_latent_chunk_attention(*small, scale=att.scale)
    gap = float(jnp.abs(got.astype(jnp.float32)
                        - twin.astype(jnp.float32)).max())
    say(latent_chunk_kernel="against its gather twin", widest=gap)
    check(gap < 0.05, "the expanded kernel and its twin differ by", gap)
    for off in (0, 128, 384) if tiny else (0, 6000, 14000):
        for live in (c, max(1, c * 100 // 2048)):
            call(off, live).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(5):
                out = call(off, live)
            out.block_until_ready()
            say(latent_chunk_kernel="alone", offset=off, live_rows=live,
                ms_a_call=round((time.perf_counter() - t0) / 5 * 1e3, 3))


def grouped_kernel_alone(tiny, seed):
    """The grouped product by itself at the shapes the two MoE cells send it
    (``trinitymini.serve.mixedlen``: 128 experts of 2048 x 1024, a 32-slot
    tick's 256 pairs and a 2,048-token chunk's 16,384;
    ``kimik2.serve.longshared``: 12 held experts of 7168 x 2048, a tick's 8
    held pairs of 256 and a chunk pass's ~512 of 2,048): the tile plan each
    shape takes, the kernel against its XLA twin on the live rows, ms a
    call of the whole product (tile map, scatter, kernel, gather) and the
    share of its floor, the hit experts' weights once at the HBM rate."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import grouped_matmul as G

    shapes = [("tiny", 64, 8, 128, 384, None)] if tiny else [
        ("trinity_tick_gate_up", 256, 128, 2048, 2048, None),
        ("trinity_tick_down", 256, 128, 1024, 2048, None),
        ("trinity_chunk_gate_up", 16384, 128, 2048, 2048, None),
        ("trinity_chunk_down", 16384, 128, 1024, 2048, None),
        ("kimi_tick_gate_up", 256, 12, 7168, 4096, 8),
        ("kimi_tick_down", 256, 12, 2048, 7168, 8),
        ("kimi_chunk_gate_up", 2048, 12, 7168, 4096, 512),
        ("kimi_chunk_down", 2048, 12, 2048, 7168, 512)]
    interpret = jax.default_backend() != "tpu"
    for i, (name, m, e, k, n, live) in enumerate(shapes):
        live = m if live is None else live
        sizes = np.random.RandomState(seed + i).multinomial(
            live, np.full(e, 1.0 / e)).astype(np.int32)
        key = jax.random.PRNGKey(seed + i)
        lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
        rhs = (jax.random.normal(jax.random.fold_in(key, 1), (e, k, n),
                                 jnp.float32) * 0.02).astype(jnp.bfloat16)
        gs = jnp.asarray(sizes)
        fn = jax.jit(lambda a, b, g: G.grouped_matmul(
            a, b, g, impl="pallas", interpret=interpret))
        got = fn(lhs, rhs, gs)[:live].astype(jnp.float32)
        twin = G.grouped_matmul(lhs, rhs, gs, impl="xla")[:live].astype(
            jnp.float32)
        gap = float(jnp.abs(got - twin).max()) if live else 0.0
        check(gap <= 0.02 * max(1.0, float(jnp.abs(twin).max())),
              "the grouped kernel and its XLA twin differ by", gap, name)
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(lhs, rhs, gs)
        out.block_until_ready()
        ms = (time.perf_counter() - t0) / 10 * 1e3
        floor_ms = int((sizes > 0).sum()) * k * n * 2 / 819e9 * 1e3
        say(grouped_kernel=name, plan=list(G.tile_plan(m, e, k, n,
                                                       jnp.bfloat16)),
            experts_hit=int((sizes > 0).sum()), widest_gap_to_twin=gap,
            ms_a_call=round(ms, 3), weights_once_ms=round(floor_ms, 3),
            **({} if interpret else
               {"share_of_floor": round(floor_ms / ms, 3)}))
        del lhs, rhs, got, twin, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy widths; never prints the "
                         "chip's result line")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip legs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--latent", action="store_true",
                    help="run only the latent (MLA) leg: the program that "
                         "hung a chip in PR 41; run it under timeout")
    ap.add_argument("--grouped", action="store_true",
                    help="run only the grouped expert product, alone, at "
                         "the two MoE cells' shapes")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    dev = devs[0]
    if not args.tiny and dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} device(s)",
              file=sys.stderr)
        return 2

    import jaxlib

    from paddle_tpu.core.device import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    before = cache_entries(cache_dir)
    _listen()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    say(jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        platform=dev.platform, device_kind=dev.device_kind,
        devices=len(devs), tiny=args.tiny, chips=args.chips, seed=args.seed,
        compile_cache_dir=cache_dir, cache_entries_before=before,
        cache_warm=before > 0, memory=mem(dev))

    if args.latent:
        latent(args.tiny, args.seed)
    elif args.grouped:
        grouped_kernel_alone(args.tiny, args.seed)
    elif args.chips == 1:
        check_block_until_ready(args.tiny)
        serve(args.tiny, args.seed, dev)
        train(args.tiny, args.seed, dev)
    else:
        multichip(args.tiny, args.seed, args.chips)

    say(compile_cache_dir=cache_dir, cache_entries_before=before,
        cache_entries_after=cache_entries(cache_dir),
        compile_s_total=round(_compile["seconds"], 3),
        cache_hits=_compile["cache_hits"],
        cache_misses=_compile["cache_misses"], memory=mem(dev))
    if args.tiny:
        say(rehearsal="passed", tiny=True, platform=dev.platform)
    else:
        say(ok=True, device={"platform": dev.platform,
                             "kind": dev.device_kind,
                             "count": args.chips})
    return 0


if __name__ == "__main__":
    sys.exit(main())
