"""Fused/accelerated ops (ref: ``paddle/phi/kernels/fusion/`` +
``paddle.incubate.nn.functional``).

On TPU most "fusion" is XLA's job; the functions here exist to (a) provide
the reference's fused-op API surface and (b) dispatch to hand-written Pallas
kernels where XLA's default schedule leaves HBM bandwidth on the table
(flash attention, long-row RMSNorm).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention import (
    apply_rope,
    flash_attention,
    fused_bias_dropout_residual_layer_norm,
    fused_rotary_position_embedding,
    rope_cos_sin,
    scaled_dot_product_attention,
    xla_attention,
)


def fused_rms_norm(x, weight=None, epsilon=1e-6):
    """Dispatch: Pallas kernel on TPU for long rows, else jnp (XLA fuses it)."""
    from paddle_tpu.ops.pallas import mosaic_kernels_apply
    if (weight is not None and x.shape[-1] % 128 == 0
            and x.shape[-1] >= 512 and mosaic_kernels_apply()):
        from paddle_tpu.ops.pallas.norms import rms_norm as pallas_rms
        return pallas_rms(x, weight, epsilon)
    from paddle_tpu.nn.functional import rms_norm
    return rms_norm(x, weight, epsilon)


def fused_layer_norm(x, weight=None, bias=None, epsilon=1e-5):
    from paddle_tpu.nn.functional import layer_norm
    return layer_norm(x, x.shape[-1], weight, bias, epsilon)


def fused_linear(x, weight, bias=None, transpose_weight=False):
    if transpose_weight:
        weight = weight.T
    y = x @ weight
    return y if bias is None else y + bias


def fused_linear_activation(x, weight, bias=None, activation="gelu"):
    y = fused_linear(x, weight, bias)
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu,
           "none": lambda v: v}[activation]
    return act(y)


def fused_dropout_add(x, y, p=0.0, training=True, rng=None):
    from paddle_tpu.nn.functional import dropout
    return dropout(x, p, training=training, rng=rng) + y


def swiglu(x, y=None):
    from paddle_tpu.nn.functional import swiglu as _swiglu
    return _swiglu(x, y)
