"""Grouped (ragged) matmul for MoE expert computation — the kernel behind
dropless mixture-of-experts (ref: Paddle's ``incubate/nn/functional/moe``
surface — ``moe_dispatch`` / ``moe_ffn`` / ``moe_combine`` — whose FFN leg
this replaces; MegaBlocks, Gale et al. 2023, for the dropless formulation).

``grouped_matmul(lhs, rhs, group_sizes)`` computes, for rows of ``lhs``
sorted so that each expert's tokens are contiguous,

    out[r] = lhs[r] @ rhs[g(r)]        g(r) = the group (expert) owning row r,

i.e. one matmul per expert over a ragged row partition described by
``group_sizes`` — without the ``(tokens, experts, capacity)`` one-hot
dispatch the dense GShard path pays for. Capacity padding disappears:
FLOPs track ``sum(group_sizes)`` (= tokens x top-k), not
``experts x capacity``.

Layout strategy (TPU kernel): each expert's row segment is padded up to a
multiple of ``block_m`` so every row tile belongs to exactly ONE expert.
The padded row count is bounded statically by ``m + experts*block_m``, so
shapes stay static while the *live* tile count is a traced scalar. The
grid is (col-tile, row-tile) with the row dimension innermost; two scalar-
prefetch arrays (``tile->expert`` id map and the live-tile count) steer the
BlockSpec index maps:

  * empty experts own zero tiles — their weights are never fetched and no
    grid step touches them (the "skip empty tiles" property);
  * consecutive tiles of the same expert map to the same ``rhs`` block, so
    Mosaic's revisit rule fetches each expert's weights once per column
    tile (the "read weights once per tile" property);
  * trailing dead grid steps clamp every index map to the last live tile —
    a consecutive revisit of an already-final output block, which Mosaic
    neither recomputes nor re-flushes (`pl.when` skips the body).

Backward is two more grouped products (``custom_vjp``): ``dlhs`` reuses the
forward kernel against ``rhs`` transposed; ``drhs`` runs a second kernel
with the row dimension innermost under (k-tile, n-tile) so per-expert
partial products accumulate in the revisited output block.

Three implementations share the API:
  * ``impl="pallas"``  — the TPU kernel above (``interpret=`` runs it on
    CPU through the Pallas interpreter for kernel-parity tests);
  * ``impl="xla"``     — same sort+segment layout lowered to one batched
    matmul over row tiles with per-tile gathered weights (the fast
    non-TPU path; measured 2.4x over dense dropless on CPU);
  * ``impl="dense"``   — the one-hot ``jnp.einsum`` reference.
``PT_GROUPED_GEMM=0`` routes every call to the dense reference (read at
trace time — re-trace after flipping, e.g. ``models.paged.clear_jit_caches``).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import mosaic_kernels_apply

__all__ = ["grouped_matmul", "grouped_matmul_reference", "grouped_gemm_enabled"]

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 512
_float0 = jax.dtypes.float0


def grouped_gemm_enabled() -> bool:
    """Kill switch: ``PT_GROUPED_GEMM=0`` restores the dense path."""
    return os.environ.get("PT_GROUPED_GEMM", "1") != "0"


def _fit(blk, n):
    """Largest power-of-two divisor of ``n`` that is <= ``blk``."""
    while n % blk:
        blk //= 2
    return max(blk, 1)


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """Dense one-hot einsum reference: O(m*e*k*n), exact semantics."""
    m = lhs.shape[0]
    e = rhs.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    gid = jnp.searchsorted(ends, jnp.arange(m, dtype=jnp.int32), side="right")
    onehot = jax.nn.one_hot(gid, e, dtype=lhs.dtype)
    return jnp.einsum("me,mk,ekn->mn", onehot, lhs, rhs)


def _plan(m, e, group_sizes, bm):
    """Static-shape tile plan over the ragged row partition.

    Returns ``(gid, total, dest, w)`` where ``w = ceil(m/bm) + e`` is the
    static tile-count bound, ``total`` (traced) is the live tile count,
    ``gid[w]`` maps each tile slot to its expert (clamped past ``total`` so
    dead grid steps revisit the last live blocks), and ``dest[r]`` is row
    r's position in the segment-aligned padded buffer of ``w*bm`` rows.
    """
    sizes = group_sizes.astype(jnp.int32)
    padded = ((sizes + bm - 1) // bm) * bm
    tile_ends = jnp.cumsum(padded // bm)
    total = tile_ends[-1]
    w = -(-m // bm) + e
    w_ids = jnp.minimum(jnp.arange(w, dtype=jnp.int32),
                        jnp.maximum(total - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(tile_ends, w_ids, side="right"),
                      e - 1).astype(jnp.int32)      # e: no live tile at all
    ends = jnp.cumsum(sizes)
    shift = (jnp.cumsum(padded) - padded) - (ends - sizes)
    row_gid = jnp.searchsorted(ends, jnp.arange(m, dtype=jnp.int32),
                               side="right")
    dest = jnp.arange(m, dtype=jnp.int32) + shift[jnp.minimum(row_gid, e - 1)]
    return gid, total, dest, w


# --------------------------------------------------------------------- xla
def _xla_grouped(lhs, rhs, group_sizes, bm):
    """Sort+segment layout lowered to plain XLA: scatter rows into
    expert-aligned ``bm``-row tiles, gather each tile's expert weights,
    one batched matmul. Differentiable by construction."""
    m, k = lhs.shape
    e, _, n = rhs.shape
    gid, _, dest, w = _plan(m, e, group_sizes, bm)
    xp = jnp.zeros((w * bm, k), lhs.dtype).at[dest].set(lhs)
    yt = jnp.einsum("wbk,wkn->wbn", xp.reshape(w, bm, k), rhs[gid],
                    preferred_element_type=jnp.float32)
    return yt.reshape(w * bm, n).astype(lhs.dtype)[dest]


# ------------------------------------------------------------------ pallas
def _fwd_kernel(gid_ref, tot_ref, x_ref, w_ref, o_ref):
    del gid_ref
    wi = pl.program_id(1)

    @pl.when(wi < tot_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _pallas_fwd(lhs, rhs, group_sizes, block_m, block_n, interpret,
                name="grouped_matmul"):
    m, k = lhs.shape
    e, _, n = rhs.shape
    bm, bn = block_m, _fit(block_n, n)
    gid, total, dest, w = _plan(m, e, group_sizes, bm)
    xp = jnp.zeros((w * bm, k), lhs.dtype).at[dest].set(lhs)

    def last_live(wi, tot_ref):
        # no live tile at all (every group empty): block 0, never written
        return jnp.minimum(wi, jnp.maximum(tot_ref[0] - 1, 0))

    def xmap(ni, wi, gid_ref, tot_ref):
        del ni, gid_ref
        return last_live(wi, tot_ref), 0

    def wmap(ni, wi, gid_ref, tot_ref):
        return gid_ref[last_live(wi, tot_ref)], 0, ni

    def omap(ni, wi, gid_ref, tot_ref):
        del gid_ref
        return last_live(wi, tot_ref), ni

    yp = pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // bn, w),
            in_specs=[pl.BlockSpec((bm, k), xmap),
                      pl.BlockSpec((1, k, bn), wmap)],
            out_specs=pl.BlockSpec((bm, bn), omap)),
        out_shape=jax.ShapeDtypeStruct((w * bm, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name=name,        # the kernel's name in a device trace
    )(gid, total.reshape(1), xp, rhs)
    return yp[dest]


def _dw_kernel(gid_ref, tot_ref, x_ref, g_ref, o_ref):
    wi = pl.program_id(2)

    @pl.when(wi < tot_ref[0])
    def _():
        contrib = jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        first = (wi == 0) | (gid_ref[wi] != gid_ref[jnp.maximum(wi - 1, 0)])

        @pl.when(first)
        def _():
            o_ref[0] = contrib

        @pl.when(~first)
        def _():
            o_ref[0] += contrib


def _pallas_dw(lhs, g, group_sizes, block_m, block_n, block_k, interpret):
    """drhs[e] = lhs[seg(e)].T @ g[seg(e)] — row tiles innermost so each
    expert's output block accumulates across consecutive revisits."""
    m, k = lhs.shape
    n = g.shape[1]
    e = group_sizes.shape[0]
    bm, bk, bn = block_m, _fit(block_k, k), _fit(block_n, n)
    gid, total, dest, w = _plan(m, e, group_sizes, bm)
    xp = jnp.zeros((w * bm, k), lhs.dtype).at[dest].set(lhs)
    gp = jnp.zeros((w * bm, n), g.dtype).at[dest].set(g)

    def xmap(ki, ni, wi, gid_ref, tot_ref):
        del ni, gid_ref
        return jnp.minimum(wi, tot_ref[0] - 1), ki

    def gmap(ki, ni, wi, gid_ref, tot_ref):
        del ki, gid_ref
        return jnp.minimum(wi, tot_ref[0] - 1), ni

    def omap(ki, ni, wi, gid_ref, tot_ref):
        return gid_ref[jnp.minimum(wi, tot_ref[0] - 1)], ki, ni

    dw = pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // bk, n // bn, w),
            in_specs=[pl.BlockSpec((bm, bk), xmap),
                      pl.BlockSpec((bm, bn), gmap)],
            out_specs=pl.BlockSpec((1, bk, bn), omap)),
        out_shape=jax.ShapeDtypeStruct((e, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name="grouped_matmul_dw",
    )(gid, total.reshape(1), xp, gp)
    # blocks of never-visited (empty) experts are uninitialised memory
    return jnp.where((group_sizes > 0)[:, None, None], dw, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm(lhs, rhs, group_sizes, block_m, block_n, block_k, interpret):
    return _pallas_fwd(lhs, rhs, group_sizes, block_m, block_n, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, block_m, block_n, block_k, interpret):
    out = _pallas_fwd(lhs, rhs, group_sizes, block_m, block_n, interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(block_m, block_n, block_k, interpret, res, g):
    lhs, rhs, group_sizes = res
    dlhs = _pallas_fwd(g, rhs.transpose(0, 2, 1).astype(rhs.dtype),
                       group_sizes, block_m, block_n, interpret,
                       name="grouped_matmul_dx")
    drhs = _pallas_dw(lhs, g, group_sizes, block_m, block_n, block_k,
                      interpret).astype(rhs.dtype)
    return dlhs, drhs, np.zeros(group_sizes.shape, _float0)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ------------------------------------------------------------------ public
def grouped_matmul(lhs, rhs, group_sizes, *, block_m=DEFAULT_BLOCK_M,
                   block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
                   interpret=None, impl=None):
    """Ragged grouped matmul: ``out[r] = lhs[r] @ rhs[expert(r)]``.

    Args:
      lhs: ``[m, k]`` rows sorted so each expert's tokens are contiguous;
        ``sum(group_sizes)`` must equal ``m`` (rows past the ragged total
        produce unspecified output — callers that pad must mask).
      rhs: ``[experts, k, n]`` per-expert weights.
      group_sizes: ``[experts]`` int rows per expert (traced; zeros fine).
      interpret: run the Pallas kernel in interpreter mode; ``None`` picks
        interpret off-TPU (only consulted when ``impl="pallas"``).
      impl: ``"pallas"`` | ``"xla"`` | ``"dense"``; ``None`` auto-selects
        pallas on TPU and the xla tile-batch path elsewhere.

    Returns ``[m, n]`` in ``lhs.dtype`` (f32 accumulation on the MXU).
    """
    if lhs.ndim != 2 or rhs.ndim != 3 or rhs.shape[1] != lhs.shape[1]:
        raise ValueError(f"bad grouped_matmul shapes {lhs.shape} {rhs.shape}")
    if group_sizes.shape != (rhs.shape[0],):
        raise ValueError(f"group_sizes {group_sizes.shape} != "
                         f"({rhs.shape[0]},)")
    if not grouped_gemm_enabled():
        impl = "dense"
    if impl is None:
        impl = "pallas" if mosaic_kernels_apply() else "xla"
    if impl == "dense":
        return grouped_matmul_reference(lhs, rhs, group_sizes)
    if impl == "xla":
        # XLA tiles need no MXU alignment — shrink them until the
        # per-expert padding waste (up to experts*block_m rows) stops
        # dominating the ~m useful rows, or decode-sized calls pay the
        # dense path's experts*capacity bill all over again
        bm = block_m
        while bm > 8 and rhs.shape[0] * bm > lhs.shape[0]:
            bm //= 2
        return _xla_grouped(lhs, rhs, group_sizes.astype(jnp.int32), bm)
    if impl != "pallas":
        raise ValueError(f"unknown grouped_matmul impl {impl!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                block_m, block_n, block_k, bool(interpret))
