"""Pallas fused norms (ref: ``paddle/phi/kernels/fusion/fused_rms_norm`` /
``fused_layernorm``). One HBM read, fp32 accumulation on the VPU, bf16 out.
Rows are processed in (block_rows, hidden) tiles — hidden stays whole so the
reduction never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rms_fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm(x, weight, epsilon=1e-6, interpret=None):
    return _rms_fwd(x, weight, epsilon, interpret)[0]


def _rows(x):
    r = 1
    for s in x.shape[:-1]:
        r *= s
    return r


def _block_rows(rows, h, itemsize):
    """Rows per tile: at most 256, and few enough that the in and out
    tiles (double-buffered) plus two f32 temporaries fit the 16 MiB of
    scoped VMEM. Rows are independent, so a ragged last tile is harmless:
    what it reads past the end is never written back."""
    block = min(256, (16 << 20) // (h * (4 * itemsize + 8)) // 8 * 8)
    if block < 8:
        raise ValueError(f"rms_norm: a row of {h} does not fit a VMEM tile")
    return rows if rows <= block else block


def _rms_fwd(x, weight, epsilon, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    h = x.shape[-1]
    x2 = x.reshape(_rows(x), h)
    rows = x2.shape[0]
    block = _block_rows(rows, h, x.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=epsilon),
        grid=(pl.cdiv(rows, block),),
        in_specs=[pl.BlockSpec((block, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), x.dtype),
        interpret=interpret,
        name="rms_norm_fwd",
    )(x2, weight)
    return out.reshape(x.shape), (x, weight)


def _rms_bwd(epsilon, interpret, res, g):
    x, weight = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + epsilon)
    xhat = x32 * inv
    dw = jnp.sum(g32 * xhat, axis=tuple(range(x.ndim - 1)))
    gw = g32 * w32
    h = x.shape[-1]
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dw.astype(weight.dtype)


rms_norm.defvjp(lambda x, w, e, i: _rms_fwd(x, w, e, i), _rms_bwd)
