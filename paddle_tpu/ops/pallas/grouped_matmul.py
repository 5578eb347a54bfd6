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
shapes stay static while the *live* tile count is a traced scalar. Two
scalar-prefetch arrays (the ``tile->expert`` map and the live-tile count,
``_plan``: compares against the cumulative ends and sums, no search and so
no loop on the device) steer the BlockSpec index maps; an input block is a
whole ``(block_m, k)`` row tile, a weight block ``(1, k, block_n)``, and
the product accumulates in float32 over all of ``k`` in one step.

The grid is ``(n / block_n, tiles)`` with the row tiles innermost:
consecutive tiles of one expert revisit its weight block, so an expert's
weights are read once per column tile, and every input tile is read again
per column tile. With ``T`` live row tiles of ``H`` hit experts the index
maps move, in elements,

    (n / block_n) * T * block_m * k  +  H * k * n  +  T * block_m * n

(inputs, weights, outputs): the weights once, which is the floor, and the
inputs as often as there are column tiles. The two tile sizes
(``tile_plan``) are a function of the call's static shape ``(m, experts,
k, n)`` and dtype, fixed when the call is traced, and keep the first term
small beside the second:

  * ``block_m`` from the rows an expert can expect, ``m / experts``: the
    dtype's smallest legal row tile (16 rows of bfloat16) for a decode
    tick's two rows an expert, doubling while a tile of padding an expert
    adds at most half the rows (64 where a chunk call sends an expert
    ~128), up to 128. The padded buffer is ``(ceil(m/block_m) + experts) x
    block_m`` rows: 2,304 for 256 rows over 128 experts at 16, not 16,640;
    24,576 for 16,384 rows at 64, not 32,768 (measured on the v5e: what
    stands around the kernel, the gather into that buffer and out of it,
    costs more than the taller tile saves on the MXU).
  * ``block_n`` the widest power-of-two divisor of ``n`` whose two weight
    buffers, two input tiles, two output tiles and float32 product fit 12
    of the compiler's DEFAULT 16 MiB of scoped VMEM (never raised): 1,024
    columns at ``k`` 2,048 (a 4 MB block in 2 KB segments, two column
    tiles, so a 64 KB input tile read twice), 256 at 7,168.

The other order (column tiles innermost, an input tile read once and an
expert's weights once per ROW TILE) was measured at the one cell shape
whose bytes favour it, a tick's gate/up product, and moved nothing (PERF.md
section 6, PR 45): it is not here.

  * empty experts own zero tiles — their weights are never fetched and no
    grid step touches them (the "skip empty tiles" property);
  * the grid steps past the live tiles clamp every index map to the LAST
    live tile's blocks — a consecutive revisit of an already-final output
    block, which Mosaic neither fetches again nor re-flushes (`pl.when`
    skips the body);
  * rows past the ragged total come out unspecified, and so does the
    gradient with respect to them.

A traced forward call site leaves ``grouped_matmul:bm<..>:bn<..>`` among
the kernels' breadcrumbs and counts itself in
``moe_grouped_matmul_plans_total{block_m, block_n}``.

Backward is two more grouped products (``custom_vjp``): ``dlhs`` reuses the
forward kernel against ``rhs`` transposed; ``drhs`` runs a second kernel
with the row dimension innermost under (k-tile, n-tile) so per-expert
partial products accumulate in the revisited output block (128-row
tiles whatever the forward took).

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
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.observability.metrics import METRICS
from paddle_tpu.ops.pallas import mosaic_kernels_apply
from paddle_tpu.ops.pallas.paged_attention import _note_trace

__all__ = ["grouped_matmul", "grouped_matmul_reference",
           "grouped_gemm_enabled", "tile_plan", "TilePlan"]

DEFAULT_BLOCK_M = 128      # the tallest row tile; the backward's tile
DEFAULT_BLOCK_N = 128      # the backward's column tile
DEFAULT_BLOCK_K = 512
# what a forward call's pipeline buffers may fill of the compiler's DEFAULT
# scoped VMEM (16 MiB on a v5e; the limit is never raised: a kernel that
# asks for another size makes XLA lay out the whole program's VMEM anew)
_VMEM_BUDGET = 12 << 20
_float0 = jax.dtypes.float0

_PLANS = METRICS.counter(
    "moe_grouped_matmul_plans_total",
    "forward grouped products traced, by the tiles their static shape "
    "chose: the row tile's height, the column tile's width (one a traced "
    "call site, not one a call)",
    labelnames=("block_m", "block_n"))


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


class TilePlan(NamedTuple):
    """A forward call's tiles, fixed when the call is traced."""
    block_m: int
    block_n: int


def tile_plan(m, e, k, n, dtype, block_m=None, block_n=None) -> TilePlan:
    """The forward kernel's tiles for ``[m, k] x [e, k, n]``: a function of
    the call's static shape and dtype, and of nothing else.

    ``block_m``: the dtype's smallest legal row tile (8 rows of 32 bits a
    sublane: 16 of bfloat16), doubled while the padding it can add, a tile
    an expert, stays within half the rows (``2 * e * block_m <= m``), up
    to ``DEFAULT_BLOCK_M``. ``block_n``: the widest
    power-of-two divisor of ``n`` whose double-buffered weight block,
    input tile and output tile, with the float32 product, stay inside
    ``_VMEM_BUDGET``."""
    item = jnp.dtype(dtype).itemsize
    bm = block_m
    if bm is None:
        bm = 8 * max(1, 4 // item)
        while bm < DEFAULT_BLOCK_M and 4 * bm * e <= m:
            bm *= 2
    if block_n is None:
        bn = n & -n
        while bn > 128 and (2 * (k * bn + bm * k + bm * bn) * item
                            + bm * bn * 4) > _VMEM_BUDGET:
            bn //= 2
    else:
        bn = _fit(block_n, n)
    return TilePlan(bm, bn)


def _plan(m, e, group_sizes, bm):
    """Static-shape tile map over the ragged row partition: each expert's
    rows padded up to whole ``bm``-row tiles, ``w = ceil(m / bm) + e`` tile
    slots of which ``total`` (traced) are live. Every map is a compare
    against cumulative ends and a sum (``w x e`` and ``m x e`` booleans):
    no search, so no loop on the device.

    Returns ``(gid, total, dest, src)``: ``gid[w]`` a tile slot's expert
    (past ``total`` the last live tile's, so dead grid steps revisit its
    blocks), ``dest[m]`` a row's place in the padded buffer of ``w * bm``
    rows, ``src[w * bm]`` the row a padded place holds (padding: any)."""
    sizes = group_sizes.astype(jnp.int32)
    tiles = (sizes + bm - 1) // bm
    pad = tiles * bm - sizes
    tile_ends = jnp.cumsum(tiles)
    total = tile_ends[-1]
    w = -(-m // bm) + e
    slots = jnp.arange(w, dtype=jnp.int32)
    # a tile's expert: the experts whose tiles end at or before it
    gid = jnp.minimum(
        jnp.sum(jnp.minimum(slots, jnp.maximum(total - 1, 0))[:, None]
                >= tile_ends[None, :], axis=1, dtype=jnp.int32),
        e - 1)                                      # e: no live tile at all
    # a row moves down by the padding of every expert that ends at or
    # before it (rows past the ragged total: by all of it), and a padded
    # place looks back by the padding of the experts before its tile's
    rows = jnp.arange(m, dtype=jnp.int32)
    dest = rows + jnp.sum(
        jnp.where(rows[:, None] >= jnp.cumsum(sizes)[None, :], pad[None, :],
                  0), axis=1, dtype=jnp.int32)
    back = jnp.sum(jnp.where(slots[:, None] >= tile_ends[None, :],
                             pad[None, :], 0), axis=1, dtype=jnp.int32)
    src = jnp.clip(jnp.arange(w * bm, dtype=jnp.int32) - jnp.repeat(back, bm),
                   0, m - 1)
    return gid, total, dest, src


# --------------------------------------------------------------------- xla
def _xla_grouped(lhs, rhs, group_sizes, bm):
    """Sort+segment layout lowered to plain XLA: scatter rows into
    expert-aligned ``bm``-row tiles, gather each tile's expert weights,
    one batched matmul. Differentiable by construction."""
    m, k = lhs.shape
    e, _, n = rhs.shape
    gid, _, dest, _ = _plan(m, e, group_sizes, bm)
    w = gid.shape[0]
    xp = jnp.zeros((w * bm, k), lhs.dtype).at[dest].set(lhs)
    yt = jnp.einsum("wbk,wkn->wbn", xp.reshape(w, bm, k), rhs[gid],
                    preferred_element_type=jnp.float32)
    return yt.reshape(w * bm, n).astype(lhs.dtype)[dest]


# ------------------------------------------------------------------ pallas
def _fwd_kernel(gid_ref, tot_ref, x_ref, w_ref, o_ref):
    del gid_ref

    @pl.when(pl.program_id(1) < tot_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _pallas_fwd(lhs, rhs, group_sizes, plan, interpret,
                name="grouped_matmul"):
    m, k = lhs.shape
    e, _, n = rhs.shape
    bm, bn = plan
    gid, total, dest, src = _plan(m, e, group_sizes, bm)
    w = gid.shape[0]
    # the padded buffer by a gather (a padding place holds some row or
    # other: its product is never read), which the chip does at several
    # times the rate of a zero-fill and a scatter
    xp = lhs[src]

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
    gid, total, dest, _ = _plan(m, e, group_sizes, bm)
    w = gid.shape[0]
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, plan, blocks, interpret):
    """``plan``: the forward's tiles; ``blocks`` the caller's explicit
    ``(block_m, block_n, block_k)``, None where it gave none."""
    return _pallas_fwd(lhs, rhs, group_sizes, plan, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, plan, blocks, interpret):
    out = _pallas_fwd(lhs, rhs, group_sizes, plan, interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(plan, blocks, interpret, res, g):
    lhs, rhs, group_sizes = res
    (m, k), (e, _, n) = lhs.shape, rhs.shape
    block_m, block_n, block_k = blocks
    dlhs = _pallas_fwd(g, rhs.transpose(0, 2, 1).astype(rhs.dtype),
                       group_sizes,
                       tile_plan(m, e, n, k, g.dtype, block_m, block_n),
                       interpret, name="grouped_matmul_dx")
    drhs = _pallas_dw(lhs, g, group_sizes, block_m or DEFAULT_BLOCK_M,
                      block_n or DEFAULT_BLOCK_N, block_k or DEFAULT_BLOCK_K,
                      interpret).astype(rhs.dtype)
    return dlhs, drhs, np.zeros(group_sizes.shape, _float0)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ------------------------------------------------------------------ public
def grouped_matmul(lhs, rhs, group_sizes, *, block_m=None, block_n=None,
                   block_k=None, interpret=None, impl=None):
    """Ragged grouped matmul: ``out[r] = lhs[r] @ rhs[expert(r)]``.

    Args:
      lhs: ``[m, k]`` rows sorted so each expert's tokens are contiguous;
        ``sum(group_sizes)`` must equal ``m`` (rows past the ragged total
        produce unspecified output — callers that pad must mask).
      rhs: ``[experts, k, n]`` per-expert weights.
      group_sizes: ``[experts]`` int rows per expert (traced; zeros fine).
      block_m, block_n: the forward kernel's row and column tile; None
        (every caller in the tree) takes :func:`tile_plan`'s from the
        call's shape. ``block_k`` is the backward's alone.
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
    group_sizes = group_sizes.astype(jnp.int32)
    (m, k), (e, _, n) = lhs.shape, rhs.shape
    if impl == "xla":
        # XLA tiles need no MXU alignment — shrink them until the
        # per-expert padding waste (up to experts*block_m rows) stops
        # dominating the ~m useful rows, or decode-sized calls pay the
        # dense path's experts*capacity bill all over again
        bm = block_m or DEFAULT_BLOCK_M
        while bm > 8 and e * bm > m:
            bm //= 2
        return _xla_grouped(lhs, rhs, group_sizes, bm)
    if impl != "pallas":
        raise ValueError(f"unknown grouped_matmul impl {impl!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # the choice is made when the call is traced, and recorded there
    plan = tile_plan(m, e, k, n, lhs.dtype, block_m, block_n)
    _note_trace(f"grouped_matmul:bm{plan.block_m}:bn{plan.block_n}")
    _PLANS.inc(block_m=plan.block_m, block_n=plan.block_n)
    return _gmm(lhs, rhs, group_sizes, plan, (block_m, block_n, block_k),
                bool(interpret))
