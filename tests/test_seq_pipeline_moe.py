"""Ring attention == full attention; pipeline == sequential; MoE dispatch
conservation (SURVEY.md §4)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu.distributed import HybridMesh
from paddle_tpu.distributed.moe import MoELayer, top_k_gate
from paddle_tpu.distributed.pipeline import PipelineLayer, stack_layers
from paddle_tpu.distributed.ring_attention import make_ring_attention, ring_attention
from paddle_tpu.ops.attention import xla_attention


@pytest.mark.parametrize(
    "causal", [False, pytest.param(True, marks=pytest.mark.slow)])
def test_ring_attention_matches_full(causal):
    b, s, h, d = 2, 32, 2, 8
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    ref = xla_attention(q, k, v, is_causal=causal)
    mesh = HybridMesh(sp=8)
    with mesh:
        attend = make_ring_attention(mesh, causal=causal)
        out = attend(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_ring_attention_grad_matches_full():
    b, s, h, d = 1, 16, 2, 4
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))

    ref_grads = jax.grad(lambda q, k, v: jnp.sum(xla_attention(q, k, v, is_causal=True) ** 2),
                         argnums=(0, 1, 2))(q, k, v)
    mesh = HybridMesh(sp=8)
    with mesh:
        attend = make_ring_attention(mesh, causal=True)
        got_grads = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) ** 2),
                             argnums=(0, 1, 2))(q, k, v)
    for g_ref, g_got in zip(ref_grads, got_grads):
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=5e-4, atol=5e-5)


def _mlp_block(width):
    return nn.Sequential(nn.Linear(width, width * 2), nn.GELU(), nn.Linear(width * 2, width))


def test_pipeline_matches_sequential():
    pt.seed(0)
    width = 16
    blocks = [_mlp_block(width) for _ in range(8)]
    x = jnp.asarray(np.random.RandomState(0).randn(8, width).astype(np.float32))

    ref = x
    for blk in blocks:
        ref = blk(ref)

    pipe = PipelineLayer(blocks, num_stages=4, num_microbatches=4)
    # no-mesh path (plain scan)
    out0 = pipe(x)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(ref), rtol=1e-4, atol=1e-5)

    mesh = HybridMesh(pp=4, devices=jax.devices()[:4])
    out = pipe(x, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_pipeline_grad_matches_sequential():
    pt.seed(0)
    width = 8
    blocks = [_mlp_block(width) for _ in range(4)]
    x = jnp.asarray(np.random.RandomState(0).randn(4, width).astype(np.float32))

    def seq_loss(stacked, x):
        pipe = PipelineLayer.__new__(PipelineLayer)  # reuse scan path via stacked tree
        from jax import lax
        def body(h, lyr):
            return lyr(h), None
        out, _ = lax.scan(body, x, stacked)
        return jnp.sum(out ** 2)

    stacked = stack_layers(blocks)
    ref_grad = jax.grad(seq_loss)(stacked, x)

    mesh = HybridMesh(pp=4, devices=jax.devices()[:4])
    pipe = PipelineLayer(blocks, num_stages=4, num_microbatches=2)

    def pipe_loss(stacked_params, x):
        p2 = PipelineLayer.__new__(PipelineLayer)
        object.__setattr__(p2, "_buffers", set()); object.__setattr__(p2, "_pspecs", {})
        object.__setattr__(p2, "_dyn_names", set()); object.__setattr__(p2, "training", True)
        p2.stacked = stacked_params
        p2.num_stages = 4; p2.num_microbatches = 2
        p2.layers_per_stage = 1; p2.n_layers = 4; p2.remat = True
        p2.template = blocks[0]
        return jnp.sum(p2(x, mesh=mesh) ** 2)

    got_grad = jax.jit(jax.grad(pipe_loss))(stacked, x)
    for a, b in zip(jax.tree_util.tree_leaves(ref_grad), jax.tree_util.tree_leaves(got_grad)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-3, atol=1e-4)


def test_top_k_gate_conservation():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(64, 8).astype(np.float32))
    dispatch, combine, aux = top_k_gate(logits, k=2, capacity=16)
    # each token lands in at most k slots; each (expert, slot) used at most once
    per_slot = np.asarray(dispatch).sum(axis=0).reshape(-1)
    assert per_slot.max() <= 1
    per_token = np.asarray(dispatch).sum(axis=(1, 2))
    assert per_token.max() <= 2
    # combine weights for a routed token sum to ~1 (both choices kept)
    cw = np.asarray(combine).sum(axis=(1, 2))
    routed = per_token == 2
    np.testing.assert_allclose(cw[routed], 1.0, rtol=1e-5)
    assert float(aux) > 0


def test_moe_layer_forward_backward():
    pt.seed(0)
    moe = MoELayer(hidden=16, intermediate=32, num_experts=4, k=2)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16).astype(np.float32))
    y, aux = moe(x)
    assert y.shape == x.shape
    def loss(m, x):
        y, aux = m(x)
        return jnp.mean(y ** 2) + 0.01 * aux
    lv, grads = pt.value_and_grad(loss)(moe, x)
    leaves = [l for l in jax.tree_util.tree_leaves(grads) if l is not None]
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    # expert weights get gradient (tokens actually routed)
    assert float(jnp.abs(grads.experts.gate_up).max()) > 0


def test_moe_expert_parallel_matches_single():
    pt.seed(0)
    moe = MoELayer(hidden=16, intermediate=32, num_experts=8, k=2)
    x = jnp.asarray(np.random.RandomState(0).randn(8, 8, 16).astype(np.float32))
    ref, _ = moe(x)
    mesh = HybridMesh(dp=2, fsdp=4)
    from paddle_tpu.distributed import shard_module
    with mesh:
        moe_s = shard_module(moe, mesh, min_size=1)
        xs = jax.device_put(x, mesh.batch_sharding())
        out, _ = jax.jit(lambda m, v: m(v))(moe_s, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_zigzag_ring_attention_matches_full():
    """Zigzag layout + ring == full causal attention (after inverse perm)."""
    import numpy as np
    import jax, jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.distributed.ring_attention import (
        zigzag_inverse_permutation, zigzag_permutation, zigzag_ring_attention)
    from paddle_tpu.ops.attention import xla_attention

    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    B, S, H, D = 2, 32, 2, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    ref = np.asarray(xla_attention(q, k, v, is_causal=True))

    perm = zigzag_permutation(S, 4)
    inv = zigzag_inverse_permutation(S, 4)
    qz, kz, vz = q[:, perm], k[:, perm], v[:, perm]

    spec = P(None, "sp", None, None)
    attend = shard_map(
        lambda a, b, c: zigzag_ring_attention(a, b, c, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = np.asarray(jax.jit(attend)(qz, kz, vz))[:, inv]
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


def test_zigzag_permutation_roundtrip():
    import numpy as np
    from paddle_tpu.distributed.ring_attention import (
        zigzag_inverse_permutation, zigzag_permutation)
    perm = zigzag_permutation(24, 3)
    inv = zigzag_inverse_permutation(24, 3)
    x = np.arange(24)
    np.testing.assert_array_equal(x[perm][inv], x)
    # rank 0 holds chunks 0 and 5 (of 6): first local half is 0..3
    np.testing.assert_array_equal(perm[:4], [0, 1, 2, 3])
    np.testing.assert_array_equal(perm[4:8], [20, 21, 22, 23])
