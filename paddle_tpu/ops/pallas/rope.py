"""Pallas fused rotary embedding (ref: ``paddle/phi/kernels/fusion/
fused_rope``). Applies rotate-half RoPE to q and k in one VMEM pass —
avoids materialising the rotated halves in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)          # [H, D] one (b, s) slice? -> see specs
    cos = cos_ref[0].astype(jnp.float32)      # [1, D/2]
    sin = sin_ref[0].astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    o = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    o_ref[0] = o.astype(o_ref.dtype)


def fused_rope(x, cos, sin, interpret=None):
    """x: [B, S, H, D]; cos/sin: [S, D/2]. Falls back to jnp when the shape
    doesn't justify a kernel launch."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = x.shape
    xr = x.reshape(b * s, h, d)
    cs = jnp.broadcast_to(cos[None], (b, s, cos.shape[-1])).reshape(b * s, 1, -1)
    sn = jnp.broadcast_to(sin[None], (b, s, sin.shape[-1])).reshape(b * s, 1, -1)
    out = pl.pallas_call(
        _rope_kernel,
        grid=(b * s,),
        in_specs=[pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 1, d // 2), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 1, d // 2), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * s, h, d), x.dtype),
        interpret=interpret,
        name="fused_rope",
    )(xr, cs, sn)
    return out.reshape(b, s, h, d)
