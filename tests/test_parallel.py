"""Parallelism correctness on the 8-device CPU mesh (SURVEY.md §4; ref
test/collective/fleet/). The gold standard: every parallel form must equal
the single-device computation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import (
    ColumnParallelLinear,
    HybridMesh,
    RowParallelLinear,
    VocabParallelEmbedding,
    parallel_cross_entropy,
    partition_specs,
    shard_module,
)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.train import make_train_step
from paddle_tpu.train.step import init_state


def _llama_setup(batch=4, seq=16):
    pt.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.concatenate([ids[:, 1:], -100 * jnp.ones((batch, 1), ids.dtype)], axis=1)
    return cfg, model, ids, labels


def test_tp_matches_single_device():
    cfg, model, ids, labels = _llama_setup()
    ref_loss = float(model.loss(ids, labels))
    mesh = HybridMesh(tp=8)
    with mesh:
        sharded = shard_module(model, mesh, min_size=1)
        loss = jax.jit(lambda m, i, l: m.loss(i, l))(sharded, ids, labels)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=2e-4)


def test_fsdp_matches_single_device():
    cfg, model, ids, labels = _llama_setup(batch=8)
    ref_loss = float(model.loss(ids, labels))
    mesh = HybridMesh(fsdp=8)
    with mesh:
        sharded = shard_module(model, mesh, min_size=1)
        ids_s = jax.device_put(ids, mesh.batch_sharding())
        labels_s = jax.device_put(labels, mesh.batch_sharding())
        loss = jax.jit(lambda m, i, l: m.loss(i, l))(sharded, ids_s, labels_s)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=2e-4)


def test_tp_logits_match_single_device():
    """LOGITS-level (not loss-level) parity under tp: catches errors that
    loss reduction could cancel out (r1 verdict weak #7)."""
    cfg, model, ids, labels = _llama_setup()
    ref = np.asarray(model(ids), np.float32)
    mesh = HybridMesh(tp=8)
    with mesh:
        sharded = shard_module(model, mesh, min_size=1)
        got = np.asarray(jax.jit(lambda m, i: m(i))(sharded, ids),
                         np.float32)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_fsdp_logits_match_single_device():
    cfg, model, ids, labels = _llama_setup(batch=8)
    ref = np.asarray(model(ids), np.float32)
    mesh = HybridMesh(fsdp=8)
    with mesh:
        sharded = shard_module(model, mesh, min_size=1)
        ids_s = jax.device_put(ids, mesh.batch_sharding())
        got = np.asarray(jax.jit(lambda m, i: m(i))(sharded, ids_s),
                         np.float32)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_tp_grads_match_single_device():
    """GRADIENT-level parity under tp — the strongest cancellation check:
    every parameter's gradient must match the single-device gradient."""
    cfg, model, ids, labels = _llama_setup()
    ref_grads = jax.grad(lambda m: m.loss(ids, labels))(model)
    mesh = HybridMesh(tp=8)
    with mesh:
        sharded = shard_module(model, mesh, min_size=1)
        got_grads = jax.jit(jax.grad(lambda m: m.loss(ids, labels)))(sharded)
    for (pr, r), (pg, g) in zip(
            jax.tree_util.tree_flatten_with_path(ref_grads)[0],
            jax.tree_util.tree_flatten_with_path(got_grads)[0]):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32),
            rtol=5e-4, atol=5e-4, err_msg=f"grad mismatch at {pr}")


def test_hybrid_training_matches_single_device():
    """dp2 x fsdp2 x tp2 training trajectory == single-device trajectory."""
    cfg, model, ids, labels = _llama_setup(batch=8)
    optimizer = opt.AdamW(learning_rate=1e-3)

    # single-device trajectory
    state = init_state(model, optimizer)
    step = make_train_step(lambda m, i, l: m.loss(i, l), optimizer, donate=False)
    losses_ref = []
    s = state
    for _ in range(3):
        s, loss = step(s, ids, labels)
        losses_ref.append(float(loss))

    # sharded trajectory
    mesh = HybridMesh(dp=2, fsdp=2, tp=2)
    with mesh:
        s2 = init_state(model, optimizer, mesh)
        ids_s = jax.device_put(ids, mesh.batch_sharding())
        labels_s = jax.device_put(labels, mesh.batch_sharding())
        step2 = make_train_step(lambda m, i, l: m.loss(i, l), optimizer, donate=False)
        losses_par = []
        for _ in range(3):
            s2, loss = step2(s2, ids_s, labels_s)
            losses_par.append(float(loss))
    np.testing.assert_allclose(losses_par, losses_ref, rtol=3e-4)


def test_init_state_is_laid_out_as_the_step_returns_it():
    """A state that comes back laid out differently compiles the step
    twice: the step counter, lr and rng key left on one device, and (at
    hidden >= 1024) the norm weights and their slots, which XLA re-shards
    over fsdp on the way out unless the step holds them to the layout the
    state carries."""
    cfg = LlamaConfig.tiny(hidden_size=1024, num_hidden_layers=1,
                           intermediate_size=256)
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (8, 32)))
    labels = ids
    optimizer = opt.AdamW(learning_rate=1e-3, multi_precision=True)
    mesh = HybridMesh(fsdp=2, tp=2, devices=jax.devices()[:4])
    with mesh:
        state = init_state(model, optimizer, mesh)
        before = [l.sharding for l in jax.tree_util.tree_leaves(state)]
        step = make_train_step(lambda m, i, l: m.loss(i, l), optimizer, mesh)
        state, _ = step(state, jax.device_put(ids, mesh.batch_sharding()),
                        jax.device_put(labels, mesh.batch_sharding()))
    after = jax.tree_util.tree_leaves(state)
    assert len(before) == len(after)
    for was, leaf in zip(before, after):
        assert len(was.device_set) == 4
        assert was.is_equivalent_to(leaf.sharding, leaf.ndim), (was, leaf.sharding)


def test_column_row_parallel_match_dense():
    pt.seed(1)
    col = ColumnParallelLinear(16, 32, gather_output=False)
    row = RowParallelLinear(32, 16)
    x = jnp.asarray(np.random.RandomState(0).randn(4, 16).astype(np.float32))
    ref = (jax.nn.relu(col(x))) @ np.asarray(row.weight) + np.asarray(row.bias)
    mesh = HybridMesh(tp=8)
    with mesh:
        col_s = shard_module(col, mesh, min_size=1)
        row_s = shard_module(row, mesh, min_size=1)
        out = jax.jit(lambda c, r, x: r(jax.nn.relu(c(x))))(col_s, row_s, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_vocab_parallel_embedding():
    pt.seed(2)
    emb = VocabParallelEmbedding(64, 8)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 10)))
    ref = emb(ids)
    mesh = HybridMesh(tp=8)
    with mesh:
        emb_s = shard_module(emb, mesh, min_size=1)
        out = jax.jit(lambda e, i: e(i))(emb_s, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_parallel_cross_entropy_matches_dense():
    logits = jnp.asarray(np.random.RandomState(0).randn(4, 32).astype(np.float32))
    labels = jnp.asarray(np.random.RandomState(1).randint(0, 32, (4,)))
    import paddle_tpu.nn.functional as F
    ref = F.cross_entropy(logits, labels, reduction="none")
    got = parallel_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


def test_partition_specs_respect_tp_annotations():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    specs = partition_specs(model, stage=3, min_size=1, fsdp_size=2)
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: x is None or isinstance(x, jax.sharding.PartitionSpec))
    named = [s for s in flat if s is not None and any(a is not None for a in s)]
    assert named, "no sharded leaves"
    tp_specs = [s for s in named if "tp" in jax.tree_util.tree_leaves(tuple(s))]
    assert tp_specs, "tp annotations not propagated"


def test_collectives_shard_map():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    import paddle_tpu.distributed as dist

    mesh = HybridMesh(dp=8)
    x = jnp.arange(8.0)

    f = shard_map(lambda v: dist.all_reduce(v, axis_name="dp"),
                  mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8,), 28.0))

    g = shard_map(lambda v: dist.all_gather(v, axis_name="dp"),
                  mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    gathered = g(x)  # each member holds the full gather; global shape 8*8
    assert gathered.shape == (64,)
    np.testing.assert_allclose(np.asarray(gathered)[:8], np.arange(8.0))

    h = shard_map(lambda v: dist.shift(v, 1, axis_name="dp"),
                  mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    np.testing.assert_allclose(np.asarray(h(x)), np.roll(np.arange(8.0), 1))
