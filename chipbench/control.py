"""The controls: what a limit of ``correct`` has to refuse. Used by
``calibrate.py`` and the tests, never by a run of the benchmark.

A control is the computation in the nearest precision below the one the
configuration states (int8 or fp8 for bfloat16). Where the program has such
a path of its own, the control is the program with that path switched on:
``quantized_serving`` wraps a builder so that the engine serves weights the
program itself has quantised (``paddle_tpu.serving.quant``); the K/V side is
the engine option ``kv_dtype``. Where it has none that has run on the chip
(training), the control is the plain reference with its tensors rounded,
put in the program's place: ``lowered`` swaps ``reference.layer`` for a copy
that rounds, so ``reference.py`` itself stays the float32 description.
"""
from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import reference


def round_to(x, dtype, axis=-1):
    """Rounding to ``dtype`` and back. int8 and fp8 are symmetric with one
    float32 scale per slice along ``axis``; a 16-bit type is a plain cast.
    Backward passes the gradient straight through, as training recipes in
    these types do: a gradient cast to them unscaled is flushed to zero."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize >= 2:
        y = x.astype(dtype).astype(jnp.float32)
    else:
        whole = jnp.issubdtype(dtype, jnp.integer)
        top = float(jnp.iinfo(dtype).max if whole else jnp.finfo(dtype).max)
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis, keepdims=True), 1e-30) / top
        y = x / scale
        y = (jnp.round(y) if whole else y).astype(dtype).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(y - x)


def _layer(x, w, *, nh, nkv, d, eps, theta, lower):
    """``reference.layer`` with every weight matrix (one scale per output
    column) and the keys and values (one scale per position and K/V head)
    rounded to ``lower``, as weight-only quantisation and a cache of that
    type would hold them; with ``+act`` after the type's name, every
    matmul's input too (one scale per position)."""
    lower, _, acts = lower.partition("+")
    act = (lambda a: round_to(a, lower)) if acts else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        w = {k: round_to(v, lower, axis=0) if v.ndim == 2 else v
             for k, v in w.items()}
        s = x.shape[0]
        hn = act(reference._rms(x, w["ln_attn"], eps))
        q = reference._rope((hn @ w["wq"]).reshape(s, nh, d), theta)
        k = reference._rope((hn @ w["wk"]).reshape(s, nkv, d), theta)
        v = (hn @ w["wv"]).reshape(s, nkv, d)
        ctx = reference.attend(q, round_to(k, lower), round_to(v, lower))
        x = x + act(ctx) @ w["wo"]
        hn = act(reference._rms(x, w["ln_mlp"], eps))
        x = x + act(jax.nn.silu(hn @ w["w_gate"]) * (hn @ w["w_up"])) @ w["w_down"]
        return x


@contextmanager
def lowered(lower):
    """Inside, ``reference.forward`` and ``reference.train`` compute their
    layers in ``lower``."""
    static = ("nh", "nkv", "d", "eps", "theta")
    layer = jax.jit(partial(_layer, lower=lower), static_argnames=static)
    layer_vjp = jax.jit(
        lambda x, w, ct, **kw: jax.vjp(
            lambda x, w: _layer(x, w, lower=lower, **kw), x, w)[1](ct),
        static_argnames=static)
    plain = reference.layer, reference.layer_vjp
    reference.layer, reference.layer_vjp = layer, layer_vjp
    try:
        yield
    finally:
        reference.layer, reference.layer_vjp = plain


def quantized_serving(build, algo):
    """-> a builder whose model carries the program's own weight-only
    quantisation (``weight_only_int8``): the control of a served cell."""
    def built(cfg, seed, **overrides):
        from paddle_tpu.serving.quant import quantize_for_serving
        return quantize_for_serving(build(cfg, seed, **overrides), algo)
    return built
