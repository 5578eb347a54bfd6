"""On-chip experiment: WHY is the banded (sliding-window) flash grid slower
than full causal? (tpu_probe round-3 finding: 51.9ms vs 8.0ms at S=4096,
w=1024 — ~20x per-iteration cost.)

Variants timed (fwd only, S=4096, w=1024, bf16):
  full        — full causal grid, pl.when skips dead tiles (the fast case)
  band_arith  — banded grid, index map computes the band start inline
                (jnp.maximum / floordiv on grid indices) [current mainline]
  band_sp     — banded grid, band starts PRECOMPUTED into an int32 array
                and read from SMEM via PrefetchScalarGridSpec (splash-
                attention pattern)
  *_par       — same, with dimension_semantics=(parallel, parallel,
                arbitrary) declared

Timing notes: sync via float() host fetch, amortized over ITERS calls.
A no-op jit's time is printed alongside as the dispatch-overhead floor —
compare variants against it, it is NOT subtracted.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = "--interpret" in sys.argv  # CPU structural smoke (tiny shapes)
B, H, D = 1, 4, 128
S = 512 if INTERPRET else 4096
W = 256 if INTERPRET else 1024
BQ = BK = 128
ITERS = 2 if INTERPRET else 20
_NEG_INF = -1e30


def _mask(s, i, j, causal=True, window=W):
    q_idx = i * BQ + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = j * BK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = q_idx >= k_idx
    if window is not None:
        keep &= (q_idx - k_idx) < window
    return jnp.where(keep, s, _NEG_INF)


def _body(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, *, i, j, jl, nsteps,
          window, live):
    @pl.when(jl == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * (D ** -0.5)
        s = _mask(s, i, j, window=window)
        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_sc[:, 0] = l_sc[:, 0] * corr + jnp.sum(p, axis=1)
        m_sc[:, 0] = m_new
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr[:, None] + pv

    pl.when(live)(compute)

    @pl.when(jl == nsteps - 1)
    def _fin():
        o_ref[0] = (acc[:] / jnp.maximum(l_sc[:], 1e-30)).astype(o_ref.dtype)


def _scratch():
    return [pltpu.VMEM((BQ, D), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32)]


def _band_start(i):
    return jnp.maximum(0, (i * BQ - W + 1) // BK)


NK = S // BK
NQ = S // BQ
N_BAND = min(NK, (W + BQ - 1) // BK + 1)


def make_full(par):
    def kernel(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc):
        i, j = pl.program_id(1), pl.program_id(2)
        live = j * BK <= i * BQ + BQ - 1
        _body(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, i=i, j=j, jl=j,
              nsteps=NK, window=W, live=live)

    sem = (pltpu.CompilerParams(dimension_semantics=(
        pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY)) if par else None)
    return pl.pallas_call(
        kernel, grid=(B * H, NQ, NK),
        in_specs=[pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), jnp.bfloat16),
        scratch_shapes=_scratch(),
        interpret=INTERPRET,
        **({"compiler_params": sem} if sem else {}),
    )


def make_band_arith(par):
    def kv_index(b, i, jl):
        return (b, jnp.minimum(_band_start(i) + jl, NK - 1), 0)

    def kernel(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc):
        i, jl = pl.program_id(1), pl.program_id(2)
        j = _band_start(i) + jl
        live = (j * BK <= i * BQ + BQ - 1) & (i * BQ - (j * BK + BK - 1) < W) \
            & (j < NK)
        _body(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, i=i, j=j, jl=jl,
              nsteps=N_BAND, window=W, live=live)

    sem = (pltpu.CompilerParams(dimension_semantics=(
        pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY)) if par else None)
    return pl.pallas_call(
        kernel, grid=(B * H, NQ, N_BAND),
        in_specs=[pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, BK, D), kv_index),
                  pl.BlockSpec((1, BK, D), kv_index)],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), jnp.bfloat16),
        scratch_shapes=_scratch(),
        interpret=INTERPRET,
        **({"compiler_params": sem} if sem else {}),
    )


def make_band_sp(par):
    """Band starts precomputed host/XLA-side; index map reads SMEM."""
    def kv_index(b, i, jl, starts_ref):
        return (b, jnp.minimum(starts_ref[i] + jl, NK - 1), 0)

    def kernel(starts_ref, q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc):
        i, jl = pl.program_id(1), pl.program_id(2)
        j = starts_ref[i] + jl
        live = (j * BK <= i * BQ + BQ - 1) & (i * BQ - (j * BK + BK - 1) < W) \
            & (j < NK)
        _body(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, i=i, j=j, jl=jl,
              nsteps=N_BAND, window=W, live=live)

    sem = (pltpu.CompilerParams(dimension_semantics=(
        pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY)) if par else None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, NQ, N_BAND),
        in_specs=[pl.BlockSpec((1, BQ, D), lambda b, i, j, s: (b, i, 0)),
                  pl.BlockSpec((1, BK, D), kv_index),
                  pl.BlockSpec((1, BK, D), kv_index)],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, i, j, s: (b, i, 0)),
        scratch_shapes=_scratch(),
    )
    inner = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), jnp.bfloat16),
        interpret=INTERPRET,
        **({"compiler_params": sem} if sem else {}),
    )
    starts = jnp.asarray(
        np.maximum(0, (np.arange(NQ) * BQ - W + 1) // BK), jnp.int32)
    return lambda q, k, v: inner(starts, q, k, v)


def timeit(f, *args):
    out = f(*args)
    float(jnp.sum(out.astype(jnp.float32)))  # compile + sync
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = f(*args)
    float(jnp.sum(out.astype(jnp.float32)))
    return (time.perf_counter() - t0) / ITERS


def main():
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B * H, S, D), jnp.bfloat16)
    k = jnp.asarray(rs.randn(B * H, S, D), jnp.bfloat16)
    v = jnp.asarray(rs.randn(B * H, S, D), jnp.bfloat16)

    # dispatch overhead calibration
    nop = jax.jit(lambda x: x + 1)
    t_nop = timeit(nop, jnp.zeros((8, 128), jnp.bfloat16))
    print(f"dispatch/no-op: {t_nop*1e3:.3f} ms", flush=True)

    # every variant computes the SAME windowed-causal attention (the full
    # grid applies the window as an in-tile mask), so outputs must agree
    ref = None
    for name, make in [
        ("full", lambda: make_full(False)),
        ("full_par", lambda: make_full(True)),
        ("band_arith", lambda: make_band_arith(False)),
        ("band_arith_par", lambda: make_band_arith(True)),
        ("band_sp", lambda: make_band_sp(False)),
        ("band_sp_par", lambda: make_band_sp(True)),
    ]:
        try:
            f = jax.jit(make())
            t = timeit(f, q, k, v)
            out = np.asarray(f(q, k, v), np.float32)
            if ref is None:
                ref = out
            err = np.abs(out - ref).max()
            print(f"{name:16s} {t*1e3:8.3f} ms  (maxdiff vs first "
                  f"{err:.4f})", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name:16s} FAIL {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
