"""Seeded random weights, made on the device by the benchmark itself.

One jitted call per layer (the same executable every time) and one for the
tensors outside the layers. A tensor is a pure function of ``(seed, layer,
name)``, so the program's builder and the plain reference draw identical
values without ever handing arrays to each other, and the reference can ask
for one layer at a time. Names and orientation follow the published model:
``y = x @ w`` with ``w`` of shape [in, out].
"""
from functools import partial

import jax
import jax.numpy as jnp

def root_key(seed: int):
    """Any whole number up to a little over 2**31 (more than int32 holds)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def layer_shapes(cfg: dict) -> dict:
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"wq": (h, nh * d), "wk": (h, nkv * d), "wv": (h, nkv * d),
            "wo": (nh * d, h), "w_gate": (h, m), "w_up": (h, m),
            "w_down": (m, h), "ln_attn": (h,), "ln_mlp": (h,)}


def _draw(key, shape, std, dtype):
    if len(shape) == 1:          # norm gains: near one, not exactly one
        return (1.0 + 0.05 * jax.random.normal(key, shape)).astype(dtype)
    return (std * jax.random.normal(key, shape)).astype(dtype)


@partial(jax.jit, static_argnames=("shapes", "std", "dtype"))
def _draw_all(key, shapes, std, dtype):
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, shape, std, dtype)
            for k, (name, shape) in zip(keys, shapes)}


def _dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def make_layer(seed: int, i: int, cfg: dict) -> dict:
    shapes = tuple(sorted(layer_shapes(cfg).items()))
    return _draw_all(jax.random.fold_in(root_key(seed), i + 1), shapes,
                     cfg["initializer_range"], _dtype(cfg))


def make_top(seed: int, cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = (("embed", (v, h)), ("head", (h, v)), ("norm", (h,)))
    return _draw_all(jax.random.fold_in(root_key(seed), 0), shapes,
                     cfg["initializer_range"], _dtype(cfg))
