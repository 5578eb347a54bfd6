"""Reference communication-API parity layer, Dirac/global initializers,
masked_multihead_attention, optimizer.set_lr."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.distributed as D
import paddle_tpu.nn as nn
import paddle_tpu.nn.initializer as I


def test_distributed_namespace_complete():
    for name in ["init_parallel_env", "get_rank", "get_world_size",
                 "all_reduce", "all_gather", "all_gather_object", "broadcast",
                 "reduce", "scatter", "alltoall", "alltoall_single", "send",
                 "recv", "isend", "irecv", "reduce_scatter", "barrier",
                 "new_group", "get_group", "wait", "spawn", "launch",
                 "ParallelEnv", "DataParallel", "fleet", "split", "ReduceOp",
                 "get_backend", "destroy_process_group", "is_initialized"]:
        assert hasattr(D, name), name


def test_group_and_env():
    g = D.new_group([0, 1, 2])
    assert g.nranks == 3 and D.get_group(g.id) is g
    assert D.is_initialized() and D.get_backend() == "xla"
    env = D.ParallelEnv()
    assert env.world_size >= 1 and env.rank == 0
    D.destroy_process_group()
    assert D.get_group(0) is None


def test_alltoall_single():
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = D.HybridMesh(dp=4, devices=jax.devices()[:4])
    x = jnp.arange(16.0).reshape(4, 4)  # member i holds row i (4 cols)

    @partial(shard_map, mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    def do(v):
        return D.alltoall_single(v.reshape(4, 1), axis_name="dp").reshape(1, 4)

    out = np.asarray(do(x))
    np.testing.assert_allclose(out, np.asarray(x).T)


def test_data_parallel_wrapper_forwards():
    pt.seed(0)
    m = nn.Linear(4, 2)
    dp = D.DataParallel(m)
    x = jnp.ones((3, 4))
    np.testing.assert_allclose(np.asarray(dp(x)), np.asarray(m(x)))
    assert dp.state_dict().keys() == m.state_dict().keys()


def test_wait_noop():
    x = jnp.ones(3)
    assert D.wait(x) is x


def test_dirac_initializer():
    w = I.Dirac()((4, 4, 3, 3))
    # channel i passes through at kernel center
    x = jnp.asarray(np.random.RandomState(0).randn(1, 4, 8, 8), jnp.float32)
    import paddle_tpu.nn.functional as F
    y = F.conv2d(x, w, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)


def test_set_global_initializer():
    I.set_global_initializer(I.Constant(2.0), I.Constant(1.0))
    try:
        lin = nn.Linear(3, 3)
        assert float(lin.weight.min()) == 2.0
        assert float(lin.bias.max()) == 1.0
    finally:
        I.set_global_initializer(None, None)
    lin2 = nn.Linear(3, 3)
    assert float(lin2.weight.min()) != 2.0


def test_masked_multihead_attention_matches_cache_decode():
    from paddle_tpu.incubate.nn import functional as IF
    rs = np.random.RandomState(0)
    b, h, d, max_len = 2, 2, 8, 6
    cache_k = jnp.zeros((b, max_len, h, d), jnp.float32)
    cache_v = jnp.zeros((b, max_len, h, d), jnp.float32)
    # fill two positions step by step, check final step vs full attention
    outs = []
    steps = [jnp.asarray(rs.randn(b, 3 * h * d).astype(np.float32))
             for _ in range(3)]
    for pos, x in enumerate(steps):
        out, cache_k, cache_v = IF.masked_multihead_attention(
            x, cache_k, cache_v, pos, num_heads=h)
        outs.append(out)
    # reference: full attention over the accumulated k/v
    from paddle_tpu.ops.attention import xla_attention
    qkv = jnp.stack(steps, axis=1).reshape(b, 3, 3 * h, d)
    q, k, v = jnp.split(qkv, 3, axis=2)  # [b, 3, h, d] each
    ref = xla_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(outs[-1]),
                               np.asarray(ref[:, -1].reshape(b, h * d)),
                               rtol=1e-5, atol=1e-5)


def test_optimizer_set_lr():
    import paddle_tpu.optimizer as opt
    o = opt.SGD(learning_rate=0.1)
    o.set_lr(0.5)
    assert o.get_lr() == 0.5
    sched = opt.StepDecay(learning_rate=0.1, step_size=10)
    o2 = opt.SGD(learning_rate=sched)
    with pytest.raises(RuntimeError):
        o2.set_lr(0.5)


def test_set_lr_takes_effect_inside_compiled_step():
    """The lr is optimizer STATE: set_lr(value, state) must change a jitted
    step's behaviour without recompilation (ADVICE r1: a Python-float lr is
    folded into the trace as a constant and set_lr silently no-ops)."""
    import paddle_tpu.optimizer as opt
    o = opt.SGD(learning_rate=0.1)
    params = {"w": jnp.ones((2,))}
    state = o.init(params)
    grads = {"w": jnp.ones((2,))}

    compiled = jax.jit(lambda p, g, s: o.step(p, g, s))
    p1, state = compiled(params, grads, state)
    np.testing.assert_allclose(np.asarray(p1["w"]), 1.0 - 0.1, rtol=1e-6)

    state = o.set_lr(0.5, state)
    assert o.get_lr(state) == 0.5
    p2, state = compiled(p1, grads, state)  # same compiled fn, new lr
    np.testing.assert_allclose(np.asarray(p2["w"]), (1.0 - 0.1) - 0.5,
                               rtol=1e-6)


def test_dist_split_linear():
    pt.seed(0)
    x = jnp.ones((2, 8))
    y = D.split(x, (8, 4), operation="linear", axis=1)
    assert y.shape == (2, 4)


def test_split_layer_retained_and_deterministic():
    import paddle_tpu.distributed as D2
    pt.seed(0)
    x = jnp.ones((2, 8))
    y1 = D2.split(x, (8, 4), operation="linear", axis=1, name="tp_fc")
    y2 = D2.split(x, (8, 4), operation="linear", axis=1, name="tp_fc")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    assert D2.get_split_layer("tp_fc") is not None


def test_destroy_single_group():
    D.destroy_process_group()
    g1 = D.new_group([0, 1])
    g2 = D.new_group([2, 3])
    D.destroy_process_group(g1)
    assert D.get_group(g1.id) is None and D.get_group(g2.id) is g2
    D.destroy_process_group()


def test_dirac_surplus_channels_zero():
    import torch
    w = np.asarray(I.Dirac()((4, 2, 3, 3)))
    ref = torch.nn.init.dirac_(torch.empty(4, 2, 3, 3)).numpy()
    np.testing.assert_allclose(w, ref)
    wg = np.asarray(I.Dirac(groups=2)((4, 2, 3, 3)))
    refg = torch.nn.init.dirac_(torch.empty(4, 2, 3, 3), groups=2).numpy()
    np.testing.assert_allclose(wg, refg)


def test_hsigmoid_accepts_2d_labels():
    import paddle_tpu.nn.functional as F
    pt.seed(0)
    layer = nn.HSigmoidLoss(8, 4)
    x = jnp.ones((3, 8))
    l1 = layer(x, jnp.asarray([0, 1, 2]))
    l2 = layer(x, jnp.asarray([[0], [1], [2]]))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2))


def test_pad_channel_last_consistent():
    import paddle_tpu.nn.functional as F
    from paddle_tpu.tensor import pad as tpad
    x = jnp.ones((1, 4, 5, 2))  # NHWC
    a = F.pad(x, [1, 1], data_format="NHWC")
    b = tpad(x, [1, 1], data_format="NHWC")
    assert a.shape == b.shape == (1, 4, 7, 2)  # W padded, C untouched
