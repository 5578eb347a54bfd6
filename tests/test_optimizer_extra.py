"""NAdam/RAdam vs torch reference steps; ASGD/Rprop semantics; LBFGS
convergence; new collectives; extra losses (SURVEY.md §2.4, §2.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt


class _OneParam(pt.Module):
    def __init__(self, w):
        super().__init__()
        self.w = jnp.asarray(w)


def _run_steps(optimizer, w0, grads_seq):
    m = _OneParam(w0)
    state = optimizer.init(m)
    for g in grads_seq:
        gm = _OneParam(jnp.asarray(g))
        m, state = optimizer.step(m, gm, state)
    return np.asarray(m.w)


def _torch_steps(torch_opt_cls, w0, grads_seq, **kw):
    import torch
    p = torch.nn.Parameter(torch.tensor(np.asarray(w0)))
    o = torch_opt_cls([p], **kw)
    for g in grads_seq:
        p.grad = torch.tensor(np.asarray(g))
        o.step()
    return p.detach().numpy()


W0 = np.array([1.0, -2.0, 3.0], np.float32)
GRADS = [np.array([0.1, -0.2, 0.3], np.float32),
         np.array([-0.05, 0.1, 0.2], np.float32),
         np.array([0.2, 0.0, -0.1], np.float32)]


def test_nadam_matches_torch():
    import torch
    got = _run_steps(opt.NAdam(learning_rate=0.01), W0, GRADS)
    want = _torch_steps(torch.optim.NAdam, W0, GRADS, lr=0.01)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_radam_matches_torch():
    import torch
    got = _run_steps(opt.RAdam(learning_rate=0.01), W0, GRADS * 4)
    want = _torch_steps(torch.optim.RAdam, W0, GRADS * 4, lr=0.01)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_rprop_matches_torch():
    import torch
    got = _run_steps(opt.Rprop(learning_rate=0.01), W0, GRADS)
    want = _torch_steps(torch.optim.Rprop, W0, GRADS, lr=0.01)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_asgd_averages_gradients():
    # batch_num=2: step uses mean of the last 2 grads
    o = opt.ASGD(learning_rate=0.1, batch_num=2)
    m = _OneParam(np.zeros(2, np.float32))
    state = o.init(m)
    g1 = _OneParam(np.array([1.0, 0.0], np.float32))
    g2 = _OneParam(np.array([0.0, 1.0], np.float32))
    m, state = o.step(m, g1, state)     # d = g1, p -= lr*d/2
    np.testing.assert_allclose(np.asarray(m.w), [-0.05, 0.0], atol=1e-6)
    m, state = o.step(m, g2, state)     # d = g1+g2
    np.testing.assert_allclose(np.asarray(m.w), [-0.1, -0.05], atol=1e-6)
    m, state = o.step(m, g2, state)     # d = g2+g2 (g1 evicted)
    np.testing.assert_allclose(np.asarray(m.w), [-0.1, -0.15], atol=1e-6)


def test_lbfgs_converges_on_quadratic():
    class M(pt.Module):
        def __init__(self):
            super().__init__()
            self.w = jnp.asarray(np.array([5.0, -3.0], np.float32))

    target = jnp.asarray(np.array([1.0, 2.0], np.float32))

    def loss_fn(m):
        d = m.w - target
        return jnp.sum(jnp.array([[2.0, 0.3], [0.3, 1.0]]) @ d * d)

    o = opt.LBFGS(learning_rate=1.0, max_iter=30, history_size=5)
    loss, m = o.minimize(loss_fn, M())
    assert float(loss) < 1e-8
    np.testing.assert_allclose(np.asarray(m.w), np.asarray(target), atol=1e-4)


def test_optimizers_jit_and_multiprecision():
    """New optimizers run under jit with bf16 params + fp32 masters."""
    # lr large enough that one step is visible at bf16 resolution
    for cls in (opt.NAdam, opt.RAdam, opt.Rprop, opt.ASGD):
        o = cls(learning_rate=0.5, multi_precision=True)
        m = _OneParam(jnp.asarray(W0, jnp.bfloat16))
        state = o.init(m)
        g = _OneParam(jnp.asarray(GRADS[0], jnp.bfloat16))
        step = jax.jit(lambda mm, gg, ss: o.step(mm, gg, ss))
        m2, state = step(m, g, state)
        assert m2.w.dtype == jnp.bfloat16
        assert not np.allclose(np.asarray(m2.w, np.float32),
                               np.asarray(m.w, np.float32))


# -- collectives -------------------------------------------------------------

def test_reduce_scatter_gather_p2p():
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import HybridMesh
    from paddle_tpu.distributed import collective as C

    mesh = HybridMesh(dp=4, devices=jax.devices()[:4])
    x = jnp.arange(8.0).reshape(4, 2)

    @partial(shard_map, mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    def do_reduce(v):
        return C.reduce(v, dst=1, op="sum", axis_name="dp")

    out = do_reduce(x)
    total = x.sum(axis=0)
    np.testing.assert_allclose(np.asarray(out[1]), total)          # dst got sum
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x[0]))  # others keep

    @partial(shard_map, mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    def do_p2p(v):
        return C.send(v, dst=2, src=0, axis_name="dp")

    out = do_p2p(x)
    np.testing.assert_allclose(np.asarray(out[2]), np.asarray(x[0]))
    np.testing.assert_allclose(np.asarray(out[3]), np.asarray(x[3]))

    ys = jnp.arange(16.0).reshape(4, 4)

    @partial(shard_map, mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    def do_scatter2(v):
        return C.scatter(v.reshape(4), src=1, axis_name="dp").reshape(1, 1)

    out = do_scatter2(ys)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.asarray(ys[1]))


def test_all_gather_object_single_process():
    from paddle_tpu.distributed.collective import all_gather_object
    assert all_gather_object({"a": 1}) == [{"a": 1}]


# -- extra losses ------------------------------------------------------------

def test_dice_loss_perfect_prediction():
    label = jnp.asarray(np.array([[0], [1]], np.int64))
    probs = jax.nn.one_hot(label.squeeze(-1), 3)
    assert float(F.dice_loss(probs, label)) < 1e-4


def test_log_loss_matches_formula():
    p = jnp.asarray([0.9, 0.2])
    y = jnp.asarray([1.0, 0.0])
    got = np.asarray(F.log_loss(p, y))
    want = -np.log(np.array([0.9 + 1e-4, 0.8 + 1e-4]))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_npair_loss_finite_and_separates():
    rs = np.random.RandomState(0)
    anchor = jnp.asarray(rs.randn(4, 8).astype(np.float32))
    labels = jnp.asarray(np.array([0, 1, 2, 3]))
    # positives identical to anchors -> similarity strongest on diagonal
    tight = float(F.npair_loss(anchor * 10, anchor * 10, labels, l2_reg=0.0))
    loose = float(F.npair_loss(anchor * 10,
                               jnp.asarray(rs.randn(4, 8).astype(np.float32)) * 10,
                               labels, l2_reg=0.0))
    assert np.isfinite(tight) and tight < loose


def test_temporal_shift_moves_channels():
    nt, c, h, w = 4, 8, 2, 2   # 2 clips x 2 frames
    x = jnp.asarray(np.arange(nt * c * h * w, dtype=np.float32)
                    .reshape(nt, c, h, w))
    out = F.temporal_shift(x, seg_num=2, shift_ratio=0.25)
    assert out.shape == x.shape
    # first quarter of channels at frame 0 now hold frame 1's values
    np.testing.assert_allclose(np.asarray(out[0, :2]), np.asarray(x[1, :2]))
    # last frame's shifted-back block is zero-padded
    np.testing.assert_allclose(np.asarray(out[1, :2]), 0.0)
    # middle quarter shifts forward
    np.testing.assert_allclose(np.asarray(out[1, 2:4]), np.asarray(x[0, 2:4]))
    # remainder untouched
    np.testing.assert_allclose(np.asarray(out[0, 4:]), np.asarray(x[0, 4:]))


def test_margin_cross_entropy_reduces_to_ce_without_margin():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(np.clip(rs.randn(4, 6), -1, 1).astype(np.float32))
    label = jnp.asarray(rs.randint(0, 6, 4))
    got = float(F.margin_cross_entropy(logits, label, margin1=1.0, margin2=0.0,
                                       margin3=0.0, scale=1.0))
    one_hot = jax.nn.one_hot(label, 6)
    want = float(jnp.mean(-jnp.sum(
        one_hot * jax.nn.log_softmax(logits, -1), -1)))
    assert abs(got - want) < 1e-5


def test_margin_cross_entropy_penalises_target():
    logits = jnp.asarray(np.array([[0.9, 0.1, -0.5]], np.float32))
    label = jnp.asarray([0])
    plain = float(F.margin_cross_entropy(logits, label, margin2=0.0, scale=8.0))
    margined = float(F.margin_cross_entropy(logits, label, margin2=0.5, scale=8.0))
    assert margined > plain  # margin makes the target harder


def test_dlpack_roundtrip():
    from paddle_tpu.utils.dlpack import from_dlpack, to_dlpack
    x = jnp.asarray(np.arange(6, dtype=np.float32).reshape(2, 3))
    y = from_dlpack(x)  # jax-to-jax via __dlpack__ protocol
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))
    import torch
    t = torch.arange(4, dtype=torch.float32)
    z = from_dlpack(t)
    np.testing.assert_allclose(np.asarray(z), t.numpy())


def test_iinfo_finfo():
    assert pt.iinfo(pt.int32).max == 2**31 - 1
    assert pt.finfo(pt.bfloat16).bits == 16


def test_set_grad_enabled_context():
    with pt.set_grad_enabled(False):
        pass
    assert pt.is_grad_enabled()
