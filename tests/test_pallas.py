"""Pallas kernels vs XLA reference, interpret mode on CPU (SURVEY.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import apply_rope, rope_cos_sin, xla_attention
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.norms import rms_norm
from paddle_tpu.ops.pallas.rope import fused_rope


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [128, 256])
def test_flash_fwd_matches_xla(causal, seq):
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(2, seq, 2, 64).astype(np.float32)) for _ in range(3))
    ref = xla_attention(q, k, v, is_causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_xla(causal):
    rs = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rs.randn(1, 128, 2, 32).astype(np.float32)) for _ in range(3))
    ref = jax.grad(lambda *a: jnp.sum(xla_attention(*a, is_causal=causal) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=causal, interpret=True) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_varlen_kv_lens_matches_masked_xla(causal):
    """Padded-varlen path: kv_lens masking == dense key-padding mask, for
    valid query rows, fwd + grads (ref flash_attn varlen capability)."""
    rs = np.random.RandomState(3)
    b, s, h, d = 3, 256, 2, 32
    lens = jnp.asarray([256, 130, 7], jnp.int32)
    q, k, v = (jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
               for _ in range(3))
    pad = (jnp.arange(s)[None, :] < lens[:, None])[:, None, None, :]
    valid_q = (jnp.arange(s)[None, :] < lens[:, None])[:, :, None, None]

    ref = xla_attention(q, k, v, attn_mask=pad, is_causal=causal)
    got = flash_attention(q, k, v, causal=causal, kv_lens=lens,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got * valid_q),
                               np.asarray(ref * valid_q),
                               rtol=1e-5, atol=1e-5)

    # grads: loss only over valid query rows (callers mask the padding)
    def loss(attend):
        def f(q, k, v):
            out = attend(q, k, v)
            return jnp.sum((out * valid_q) ** 2)
        return f

    ref_g = jax.grad(loss(lambda q, k, v: xla_attention(
        q, k, v, attn_mask=pad, is_causal=causal)), argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, kv_lens=lens, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    for r, g in zip(ref_g, got_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_flash_varlen_gqa_and_padded_rows_zero():
    """kv_lens composes with GQA; a row with ZERO valid keys (fully-masked
    softmax) emits exact zeros and finite (zero) grads, not NaN."""
    rs = np.random.RandomState(4)
    b, s, h, hkv, d = 2, 128, 4, 2, 32
    lens = jnp.asarray([128, 64], jnp.int32)
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    out = flash_attention(q, k, v, causal=False, kv_lens=lens, interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))

    # GQA + kv_lens matches repeated-KV dense-masked reference on valid rows
    pad = (jnp.arange(s)[None, :] < lens[:, None])[:, None, None, :]
    ref = xla_attention(q, k, v, attn_mask=pad, is_causal=False)
    valid_q = (jnp.arange(s)[None, :] < lens[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out * valid_q),
                               np.asarray(ref * valid_q), rtol=1e-5, atol=1e-5)

    # a row with NO valid keys: fully-masked softmax -> zero rows, zero grads
    lens0 = jnp.asarray([128, 0], jnp.int32)
    out0 = flash_attention(q, k, v, causal=False, kv_lens=lens0,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out0[1]), 0.0, atol=1e-6)
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=False, kv_lens=lens0, interpret=True) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g))), "masked rows must not NaN grads"
    np.testing.assert_allclose(np.asarray(g[1]), 0.0, atol=1e-6)


def test_sdpa_dispatch_kv_lens_xla_path():
    """scaled_dot_product_attention honours kv_lens on the XLA path too."""
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    rs = np.random.RandomState(5)
    b, s, h, d = 2, 64, 2, 16
    lens = jnp.asarray([64, 20], jnp.int32)
    q, k, v = (jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
               for _ in range(3))
    pad = (jnp.arange(s)[None, :] < lens[:, None])[:, None, None, :]
    ref = xla_attention(q, k, v, attn_mask=pad)
    got = scaled_dot_product_attention(q, k, v, kv_lens=lens)
    valid_q = (jnp.arange(s)[None, :] < lens[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.asarray(got * valid_q),
                               np.asarray(ref * valid_q), rtol=1e-5, atol=1e-5)


def test_flash_bf16():
    rs = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rs.randn(1, 128, 2, 64)).astype(jnp.bfloat16) for _ in range(3))
    ref = xla_attention(q, k, v, is_causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32), rtol=2e-2, atol=2e-2)


def test_rms_norm_kernel():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 8, 256).astype(np.float32))
    w = jnp.asarray(rs.rand(256).astype(np.float32) + 0.5)
    ref = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
    got = rms_norm(x, w, 1e-6, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
    # grads
    rg = jax.grad(lambda x, w: jnp.sum(
        (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w) ** 2),
        argnums=(0, 1))(x, w)
    gg = jax.grad(lambda x, w: jnp.sum(rms_norm(x, w, 1e-6, True) ** 2),
                  argnums=(0, 1))(x, w)
    for r, g in zip(rg, gg):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows", [5, 256, 300, 515])
def test_rms_norm_kernel_ragged_rows(rows):
    """Any row count: more than one 256-row tile with a ragged last one
    (300, 515) must equal the reference on every real row."""
    rs = np.random.RandomState(rows)
    x = jnp.asarray(rs.randn(rows, 128).astype(np.float32))
    w = jnp.asarray(rs.rand(128).astype(np.float32) + 0.5)
    ref = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
    got = rms_norm(x, w, 1e-6, True)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_fused_rope_matches_reference():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 16, 4, 64).astype(np.float32))
    cos, sin = rope_cos_sin(16, 64)
    ref = apply_rope(x, cos, sin)
    got = fused_rope(x, cos, sin, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_alibi_matches_xla(causal):
    """In-tile ALiBi (iota-computed, no O(S^2) bias tensor) == the XLA
    path's materialised additive bias, fwd + grads."""
    rs = np.random.RandomState(5)
    h = 4
    q, k, v = (jnp.asarray(rs.randn(2, 128, h, 32).astype(np.float32))
               for _ in range(3))
    slopes = jnp.asarray(2.0 ** (-np.arange(1, h + 1)), jnp.float32)

    ref = xla_attention(q, k, v, is_causal=causal, alibi_slopes=slopes)
    got = flash_attention(q, k, v, causal=causal, alibi_slopes=slopes,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    ref_g = jax.grad(lambda *a: jnp.sum(xla_attention(
        *a, is_causal=causal, alibi_slopes=slopes) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=causal, alibi_slopes=slopes, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for r, g in zip(ref_g, got_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_flash_alibi_explicit_bias_reference():
    """The slope convention is exactly bias = -m * (q_pos - k_pos)."""
    rs = np.random.RandomState(6)
    h, s = 2, 128
    q, k, v = (jnp.asarray(rs.randn(1, s, h, 32).astype(np.float32))
               for _ in range(3))
    slopes = jnp.asarray([0.5, 0.25], jnp.float32)
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    bias = jnp.asarray(-np.asarray(slopes)[None, :, None, None]
                       * (i - j)[None, None], jnp.float32)
    ref = xla_attention(q, k, v, attn_mask=bias, is_causal=True)
    got = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_alibi_gqa_decode_and_window():
    """ALiBi composes with GQA, end-aligned decode queries, a sliding
    window, and per-batch [B, H] slopes."""
    rs = np.random.RandomState(7)
    b, sk, h, hkv, d = 2, 256, 4, 2, 32
    k = jnp.asarray(rs.randn(b, sk, hkv, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, sk, hkv, d).astype(np.float32))
    slopes = jnp.asarray(rs.rand(b, h).astype(np.float32))

    # decode: 128 queries aligned to the end of the key axis
    q = jnp.asarray(rs.randn(b, 128, h, d).astype(np.float32))
    ref = xla_attention(q, k, v, is_causal=True, alibi_slopes=slopes)
    got = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # banded sliding window
    qf = jnp.asarray(rs.randn(b, sk, h, d).astype(np.float32))
    ref_w = xla_attention(qf, k, v, is_causal=True, window=64,
                          alibi_slopes=slopes)
    got_w = flash_attention(qf, k, v, causal=True, window=64,
                            alibi_slopes=slopes, interpret=True)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(ref_w),
                               rtol=1e-5, atol=1e-5)


def test_flash_alibi_varlen_decode_alignment():
    """ALiBi + kv_lens + sq < sk: query positions end-align to each row's
    VALID cache length, not the padded buffer — kernel == per-row solo."""
    rs = np.random.RandomState(9)
    b, sk, sq, h, d = 2, 256, 128, 2, 32
    q = jnp.asarray(rs.randn(b, sq, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, sk, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, sk, h, d).astype(np.float32))
    lens = jnp.asarray([256, 170], jnp.int32)
    slopes = jnp.asarray([0.5, 0.125], jnp.float32)

    got = flash_attention(q, k, v, causal=True, kv_lens=lens,
                          alibi_slopes=slopes, interpret=True)
    ref = xla_attention(q, k, v, is_causal=True, kv_lens=lens,
                        alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # row 1 must equal a solo call against its TRIMMED cache (the ground
    # truth both paths claim to implement)
    solo = xla_attention(q[1:], k[1:, :170], v[1:, :170], is_causal=True,
                         alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(solo[0]),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------- non-aligned lengths
@pytest.mark.parametrize("causal", [False, True])
def test_flash_nonaligned_length_pads_not_shrinks(causal):
    """ADVICE r4: s=1000 used to step the tile down to bq=8 (a ~64x
    smaller MXU tile); now the wrapper pads to an aligned length, masks
    the padded keys (causally or via kv_lens) and slices the tail. This
    exercises that path end-to-end: fwd + grads == XLA at s=1000."""
    rs = np.random.RandomState(7)
    s = 1000
    q, k, v = (jnp.asarray(rs.randn(1, s, 2, 32).astype(np.float32))
               for _ in range(3))
    ref = xla_attention(q, k, v, is_causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    assert got.shape == (1, s, 2, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    g_ref = jax.grad(lambda *a: jnp.sum(
        xla_attention(*a, is_causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=causal, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for r, g in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_flash_nonaligned_decode_kv_pad():
    """Decode against a non-aligned cache (sq != sk, sk=1000): K/V pad +
    introduced kv_lens keep end-aligned query positions exact."""
    rs = np.random.RandomState(8)
    q = jnp.asarray(rs.randn(2, 128, 2, 32).astype(np.float32))
    k = jnp.asarray(rs.randn(2, 1000, 2, 32).astype(np.float32))
    v = jnp.asarray(rs.randn(2, 1000, 2, 32).astype(np.float32))
    ref = xla_attention(q, k, v, is_causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_nonaligned_window():
    """Banded grid + equal q/k padding (s == sk keeps q_off == 0)."""
    rs = np.random.RandomState(9)
    s = 520
    q, k, v = (jnp.asarray(rs.randn(1, s, 2, 32).astype(np.float32))
               for _ in range(3))
    ref = xla_attention(q, k, v, is_causal=True, window=128)
    got = flash_attention(q, k, v, causal=True, window=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
