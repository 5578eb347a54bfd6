"""Grouped (ragged) GEMM vs the dense einsum reference — ISSUE 6.

Covers both implementations (the Pallas kernel through its interpret CPU
path, and the XLA tile-batch lowering) over ragged group partitions
including EMPTY experts and single-token groups, forward and backward,
plus the dropless-mode token-conservation property of the refactored
MoELayer and the PT_GROUPED_GEMM=0 kill switch (bit-compatible dense
path).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.moe import (
    MoELayer,
    expert_mlp_apply,
    grouped_forward,
    sparse_combine,
    sparse_dispatch,
    top_k_route,
)
from paddle_tpu.ops.pallas.grouped_matmul import (
    grouped_gemm_enabled,
    grouped_matmul,
    grouped_matmul_reference,
    tile_plan,
)

# (block_m, block_n) at the shapes of trinitymini.serve.mixedlen's
# tick and chunk and kimik2.serve.longshared's tick and chunk pass, gate/up
# then down: what PERF.md section 6 (PR 45) measured them at
CELL_PLANS = [
    (16, 1024), (16, 2048), (64, 1024), (64, 2048),
    (16, 256), (16, 1024), (64, 256), (64, 1024)]

def _tick_sizes():
    """128 groups of 0-5 rows, a fifth of them empty, and one of 21 (three
    tiles under a plan that expects one an expert), 256 rows in all: a
    decode tick's pairs over many small experts."""
    sizes = np.random.RandomState(45).randint(1, 6, 128)
    sizes[::5] = 0
    sizes[7] = 21
    i = 1
    while sizes.sum() != 256:       # spread what is missing or over
        step = int(np.sign(256 - sizes.sum()))
        if i % 5 and 1 <= sizes[i] + step <= 5:
            sizes[i] += step
        i = (i + 1) % 128
    return [int(v) for v in sizes]


RAGGED_CASES = [
    # (experts, k_dim, n_dim, group_sizes[, rows past the ragged total]) —
    # empty + single-token groups
    (4, 32, 64, [5, 0, 1, 10]),
    (8, 16, 32, [0, 0, 3, 1, 0, 7, 1, 0]),
    (1, 8, 128, [9]),
    (6, 64, 48, [128, 0, 1, 300, 1, 2]),     # n not a multiple of 128
    (3, 16, 16, [0, 0, 4]),                  # leading empty experts
    # ---- the regimes the tile plan tells apart (ISSUE 45); n = 384 has
    # three column tiles, so both loops of the grid turn
    (128, 32, 384, _tick_sizes()),           # many small groups, dead tiles
    (4, 64, 384, [120, 136, 128, 128]),      # a chunk's 128 rows an expert
    (2, 32, 384, [1500, 548]),               # many tiles an expert
    (8, 16, 384, [0] * 8, 24),               # no live tile at all
    (16, 32, 384, [3, 0, 1, 0, 0, 2, 0, 0, 0, 0, 1, 0, 0, 0, 0, 0]),
    (6, 16, 384, [4, 0, 9, 1, 0, 0], 19),    # rows past the ragged total
]
# what tile_plan makes of them (float32 rows: 8 a sublane tile)
PLANS = {5: (8, 128), 6: (64, 128), 7: (128, 128), 8: (8, 128),
         9: (8, 128), 10: (8, 128)}


def _case(e, k, n, sizes, past=0):
    """-> lhs, rhs, group sizes, live rows: the last ``past`` rows of lhs
    lie past the ragged total, and what comes out for them is nobody's."""
    m = sum(sizes)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m + past, k), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (e, k, n), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), m


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case", RAGGED_CASES)
def test_matches_dense_reference(impl, case):
    lhs, rhs, gs, live = _case(*case)
    ref = grouped_matmul_reference(lhs, rhs, gs)
    out = jax.jit(lambda a, b, g: grouped_matmul(a, b, g, impl=impl))(
        lhs, rhs, gs)
    assert out.shape == ref.shape
    np.testing.assert_allclose(out[:live], ref[:live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_regimes_take_the_plans_they_were_written_for(case):
    e, k, n, sizes, *past = RAGGED_CASES[case]
    assert tuple(tile_plan(sum(sizes) + sum(past), e, k, n,
                           jnp.float32)) == PLANS[case]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case", RAGGED_CASES[:3] + RAGGED_CASES[5:])
def test_gradients_match_dense_reference(impl, case):
    lhs, rhs, gs, live = _case(*case)

    def f(a, b):
        return jnp.sum(jnp.sin(grouped_matmul(a, b, gs, impl=impl)[:live]))

    def fr(a, b):
        return jnp.sum(jnp.sin(grouped_matmul_reference(a, b, gs)[:live]))

    da, db = jax.jit(jax.grad(f, argnums=(0, 1)))(lhs, rhs)
    ra, rb = jax.jit(jax.grad(fr, argnums=(0, 1)))(lhs, rhs)
    # sums over hundreds of rows differ by float32's order of summation;
    # a row past the ragged total has no gradient either
    atol = 1e-5 if case in RAGGED_CASES[:3] else 2e-4
    np.testing.assert_allclose(da[:live], ra[:live], rtol=1e-4, atol=atol)
    np.testing.assert_allclose(db, rb, rtol=1e-4, atol=atol)


def test_trailing_dead_tiles_leave_the_live_rows_alone():
    """The steps past the live tiles name the last live tile's blocks.
    More tile slots (more empty groups behind the live ones) change no
    live row's output, to the bit."""
    lhs, rhs, gs, _ = _case(4, 32, 384, [3, 1, 0, 2])
    few = grouped_matmul(lhs, rhs, gs, impl="pallas")
    wide = jnp.concatenate([rhs, jnp.full((28,) + rhs.shape[1:], jnp.nan)])
    many = grouped_matmul(lhs, wide, jnp.pad(gs, (0, 28)), impl="pallas")
    assert tile_plan(6, 32, 32, 384, jnp.float32) == (8, 128)  # 3 col tiles
    np.testing.assert_array_equal(np.asarray(few), np.asarray(many))


def test_plan_follows_the_static_shape_alone(monkeypatch):
    """(m, e, k, n, dtype) -> (block_m, block_n): the same with
    every PT_* variable unset or set; the six shapes the two MoE cells
    run; and the tile map holds no loop for the device."""
    shapes = [(256, 128, 2048, 2048), (256, 128, 1024, 2048),
              (16384, 128, 2048, 2048), (16384, 128, 1024, 2048),
              (256, 12, 7168, 4096), (256, 12, 2048, 7168),
              (2048, 12, 7168, 4096), (2048, 12, 2048, 7168)]
    for name in [v for v in os.environ if v.startswith("PT_")]:
        monkeypatch.delenv(name)
    unset = [tile_plan(*s, jnp.bfloat16) for s in shapes]
    for name in ("PT_GROUPED_GEMM", "PT_GROUPED_BLOCK_M", "PT_CP_IMPL",
                 "PT_DEGRADE"):
        monkeypatch.setenv(name, "1")
    assert [tile_plan(*s, jnp.bfloat16) for s in shapes] == unset
    assert [tuple(p) for p in unset] == CELL_PLANS
    # a tick's two rows an expert: the dtype's smallest legal row tile
    assert [tile_plan(256, 128, 2048, 2048, d).block_m
            for d in (jnp.float32, jnp.bfloat16, jnp.int8)] == [8, 16, 32]
    # an explicit tile is the caller's, as it always was
    assert tile_plan(256, 128, 2048, 2048, jnp.bfloat16, 128, 128) \
        == (128, 128)
    from paddle_tpu.ops.pallas.grouped_matmul import _plan
    text = str(jax.make_jaxpr(lambda g: tuple(_plan(256, 128, g, 16)))(
        jnp.zeros(128, jnp.int32)))
    assert "while" not in text and "sort" not in text, text


def test_expert_mlp_is_the_two_products_of_the_reference():
    """``grouped_mlp_apply`` (gate/up product, SwiGLU, down product) equals
    the same over the dense reference, forward and gradients, through the
    kernel under the interpreter."""
    from paddle_tpu.distributed import moe
    e, h, inter, sizes = 8, 32, 128, [5, 0, 1, 10, 0, 3, 2, 0]
    x, gate_up, gs, _ = _case(e, h, 2 * inter, sizes)
    gate_up = gate_up * 0.2
    down = jax.random.normal(jax.random.PRNGKey(2), (e, inter, h)) * 0.1

    def ref(a, b, c):
        gate, up = jnp.split(grouped_matmul_reference(a, b, gs), 2, axis=-1)
        return grouped_matmul_reference(jax.nn.silu(gate) * up, c, gs)

    def mlp(a, b, c):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe, "grouped_matmul", functools.partial(
                grouped_matmul, impl="pallas"))
            return moe.grouped_mlp_apply(a, b, c, gs)

    np.testing.assert_allclose(mlp(x, gate_up, down), ref(x, gate_up, down),
                               rtol=1e-4, atol=1e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    got = jax.grad(loss(mlp), argnums=(0, 1, 2))(x, gate_up, down)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(x, gate_up, down)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-3)


def test_group_sizes_is_nondiff():
    """Integer group sizes must flow float0 cotangents, not crash."""
    lhs, rhs, gs, _ = _case(*RAGGED_CASES[0])

    def f(a):
        return jnp.sum(grouped_matmul(a, rhs, gs, impl="pallas") ** 2)

    g = jax.grad(f)(lhs)
    assert g.shape == lhs.shape


def test_kill_switch_routes_to_dense(monkeypatch):
    monkeypatch.setenv("PT_GROUPED_GEMM", "0")
    assert not grouped_gemm_enabled()
    lhs, rhs, gs, _ = _case(*RAGGED_CASES[0])
    ref = grouped_matmul_reference(lhs, rhs, gs)
    out = grouped_matmul(lhs, rhs, gs, impl="pallas")  # impl overridden
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_moe_layer_kill_switch_bit_compatible(monkeypatch):
    """PT_GROUPED_GEMM=0 must restore the capacity-padded dispatch path
    bit-for-bit (same ops in the same order as the pre-grouped layer)."""
    import paddle_tpu as pt
    pt.seed(0)
    layer = MoELayer(32, 64, 4, k=2, capacity_factor=1.25)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32), jnp.float32)
    monkeypatch.setenv("PT_GROUPED_GEMM", "0")
    y_off, aux_off = jax.jit(layer)(x)

    # the dense path, composed manually — must be IDENTICAL
    t = 2 * 16
    cap = layer._capacity(t)
    xt = x.reshape(t, 32)
    logits = xt.astype(jnp.float32) @ layer.gate_w
    route, aux, _ = top_k_route(logits, 2, cap)
    x_e, dest = sparse_dispatch(xt, route, 4, cap)
    y_e = expert_mlp_apply(x_e, layer.experts.gate_up, layer.experts.down)
    yt = sparse_combine(y_e, route, dest, t)
    np.testing.assert_array_equal(np.asarray(y_off),
                                  np.asarray(yt.reshape(2, 16, 32)))
    np.testing.assert_array_equal(np.asarray(aux_off), np.asarray(aux))


def test_grouped_forward_equals_capacity_path():
    """The sorted grouped forward must reproduce the capacity path's
    results exactly in semantics (same kept/dropped set, same weights) —
    including under SATURATION, where dropped assignments must contribute
    zero."""
    import paddle_tpu as pt
    pt.seed(0)
    e, h, inter, k, t = 4, 32, 64, 2, 48
    layer = MoELayer(h, inter, e, k=k, capacity_factor=0.4)  # saturated
    x = jax.random.normal(jax.random.PRNGKey(5), (1, t, h), jnp.float32)
    xt = x.reshape(t, h)
    cap = layer._capacity(t)
    logits = xt.astype(jnp.float32) @ layer.gate_w
    route, _, drop = top_k_route(logits, k, cap)
    assert float(drop) > 0, "case must actually saturate"
    x_e, dest = sparse_dispatch(xt, route, e, cap)
    y_dense = sparse_combine(
        expert_mlp_apply(x_e, layer.experts.gate_up, layer.experts.down),
        route, dest, t)
    y_grp = grouped_forward(xt, route, layer.experts.gate_up,
                            layer.experts.down, t)
    np.testing.assert_allclose(y_grp, y_dense, rtol=1e-5, atol=1e-6)


def test_dropless_token_conservation():
    """capacity_factor=None (dropless): nothing is ever dropped and, with
    renormalised gates, each token's combine weights sum to 1 — expert
    outputs are a convex combination, so routing conserves tokens: no
    assignment mass is lost to capacity."""
    import paddle_tpu as pt
    pt.seed(0)
    e, h, k, t = 8, 16, 2, 64
    layer = MoELayer(h, 32, e, k=k, capacity_factor=None)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, t // 2, h), jnp.float32)
    y, aux, m = layer(x, return_metrics=True)
    assert float(m["drop_rate"]) == 0.0

    xt = x.reshape(t, h)
    logits = xt.astype(jnp.float32) @ layer.gate_w
    route, _, _ = top_k_route(logits, k, layer._capacity(t))
    assert bool(jnp.all(route["keep"]))
    # per-expert segment sizes cover every assignment exactly once
    assert int(jnp.sum(route["counts"])) == t * k
    # combine weights per source token sum to 1 (renormalised top-k)
    wsum = jnp.zeros((t,)).at[route["tok"]].add(route["gate"])
    np.testing.assert_allclose(wsum, np.ones(t), rtol=1e-5)
    # identity check: output equals the per-token explicit expert mix
    probs = jax.nn.softmax(logits, axis=-1)
    gv, gi = jax.lax.top_k(probs, k)
    gv = gv / jnp.sum(gv, -1, keepdims=True)
    ref = jnp.zeros_like(xt)
    for j in range(k):
        xe = expert_mlp_apply(xt[:, None, :],
                              layer.experts.gate_up[gi[:, j]],
                              layer.experts.down[gi[:, j]])[:, 0]
        ref = ref + gv[:, j][:, None] * xe
    np.testing.assert_allclose(y.reshape(t, h), ref, rtol=2e-4, atol=1e-5)
