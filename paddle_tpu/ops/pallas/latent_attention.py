"""Paged attention over a LATENT cache (multi-head latent attention, MLA:
DeepSeek-V2/V3, Kimi-K2), in the absorbed form.

A position's cache row in one layer is ``[c_kv | k_r]``: the normalised
compressed K/V latent (``kv_lora_rank`` values) and the one rotated key all
heads share (``qk_rope_head_dim`` values), side by side in ONE pool
``[num_blocks, block_size, W]`` (``latent_row_width``: the two widths
rounded up to whole 128-lane rows, the lanes past them zero). With
``W_kvb`` absorbed into the query and the output,

    score_h(t, s) = q~_h(t) . row(s) * scale      q~_h = [q_nope_h W^K_h^T | q_rope_h]
    o_h(t)        = sum_s p_h(t, s) row(s)[:kv_lora_rank]

every head attends over the SAME rows: one "K/V head" of width W whose
first ``v_width`` values are also the value. So a row is read once for all
heads and once for both uses, which is what the two kernels here do, and
what sets them apart from ``paged_attention.py``'s (a K pool and a V pool of
``[bs, H_kv, D]`` slabs; its one-head bf16 slab is off Mosaic's tiling,
while a ``[bs, W]`` slab of this pool is whole tiles at block size 16):

* **decode** (``paged_latent_decode_attention``): q ``[B, H, W]``, grid
  ``(B,)``; a loop over the row's live compute blocks, ``per_step`` pool
  blocks a copy wave into one of two VMEM slots, scores ``[H, T]`` in one
  matmul, the values a lane slice of the same buffer.
* **chunk** (``paged_latent_chunk_attention``): q ``[A, C, H, W]`` at
  positions ``offsets[a] ..``, causal over the row's pool prefix; grid
  ``(A, q tiles)``, the H heads of a position folded into the query rows
  (row r = position r // H, head r % H, which is q's own memory order),
  scores kept transposed ``[T, q_tile]`` as in the K/V chunk kernel.

Both take bf16 operands into the MXU and keep the online softmax in
float32. Dispatch is by backend and shape alone (``mosaic_kernels_apply``,
``latent_slab_is_tiled``); off the TPU the XLA gather twins run, and a test
runs a kernel through ``*_pallas(..., interpret=True)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import mosaic_kernels_apply
from paddle_tpu.ops.pallas.paged_attention import _note_trace

_NEG_INF = -1e30
# VMEM one slot of a kernel's row buffer may fill (it keeps two)
_BUFFER_BYTES = 512 * 1024
_CHUNK_Q_TILE_MAX = 1024


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Values a pool row holds: the latent and the shared rotated key, in
    whole 128-lane rows."""
    return -(-(kv_lora_rank + rope_dim) // 128) * 128


def latent_slab_is_tiled(block_size, width, v_width, dtype) -> bool:
    """Whether Mosaic can copy a ``[block_size, W]`` slab of the pool and
    slice its first ``v_width`` lanes: whole 128-lane rows, and whole
    sublane tiles of the dtype (16 rows of bf16, 8 of float32)."""
    rows = 8 * (4 // jnp.dtype(dtype).itemsize)
    return (width % 128 == 0 and v_width % 128 == 0 and v_width <= width
            and block_size % rows == 0)


def blocks_per_step(block_size, width, dtype, max_blocks) -> int:
    """Pool blocks one copy wave gathers: what ``_BUFFER_BYTES`` holds, in
    multiples of 8 blocks where it holds that many (the keys of a compute
    block then fill whole 128-lane rows of the scores)."""
    slab = block_size * width * jnp.dtype(dtype).itemsize
    p = max(1, min(_BUFFER_BYTES // slab, max_blocks))
    return p // 8 * 8 if p >= 8 else p


def _copies(tables_ref, row, pool_hbm, buf, sems, *, block_size, per_step,
            max_blocks, n_pool):
    """-> (start, wait) over compute block c into VMEM slot ``slot``: one
    ``[bs, W]`` copy for each of its table entries below ``n_live``."""
    bs, P = block_size, per_step

    def each(c, slot, n_live, act):
        def one(p, _):
            j = c * P + p

            @pl.when(j < n_live)
            def _():
                blk = jnp.minimum(
                    tables_ref[row, jnp.minimum(j, max_blocks - 1)],
                    n_pool - 1)
                act(pltpu.make_async_copy(
                    pool_hbm.at[blk], buf.at[slot, pl.ds(p * bs, bs)],
                    sems.at[slot]))
        jax.lax.fori_loop(0, P, one, None)

    return (lambda c, slot, n: each(c, slot, n, lambda cp: cp.start()),
            lambda c, slot, n: each(c, slot, n, lambda cp: cp.wait()))


# --------------------------------------------------------------- decode
def _latent_decode_kernel(tables_ref, lens_ref, q_ref, pool_hbm, o_ref, buf,
                          sems, *, block_size, scale, max_blocks, per_step,
                          v_width, n_pool):
    """Grid (B,): one step a sequence, the ``[H, W]`` query tile in VMEM,
    the pool in HBM as stored. Blocks past the live length are neither
    fetched nor walked; a sequence of length 0 emits zeros."""
    b = pl.program_id(0)
    T = per_step * block_size
    h = q_ref.shape[1]
    seq_len = lens_ref[b]
    n_live = pl.cdiv(seq_len, block_size)
    c_hi = pl.cdiv(n_live, per_step)
    start, wait = _copies(tables_ref, b, pool_hbm, buf, sems,
                          block_size=block_size, per_step=per_step,
                          max_blocks=max_blocks, n_pool=n_pool)
    # rows no copy fills meet probability 0 in the P.V matmul: they must be
    # finite, and fresh VMEM need not be
    buf[...] = jnp.zeros_like(buf)

    @pl.when(c_hi > 0)
    def _():
        start(0, 0, n_live)

    q = q_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

    def body(c, carry):
        m_prev, l_prev, acc = carry
        slot = c % 2

        @pl.when(c + 1 < c_hi)
        def _():
            start(c + 1, 1 - slot, n_live)

        wait(c, slot, n_live)
        k = buf[slot]                                     # [T, W]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = c * T + col < seq_len
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        prob = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * corr + jnp.sum(prob, axis=1, keepdims=True)
        pv = jax.lax.dot_general(prob.astype(k.dtype), k[:, :v_width],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    _, l, acc = jax.lax.fori_loop(
        0, c_hi, body,
        (jnp.full((h, 1), _NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, v_width), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_latent_decode_attention_pallas(q, pool, block_tables, lens, *,
                                         v_width, scale,
                                         interpret: bool | None = None):
    """q [B, H, W]; pool [N, bs, W]; block_tables [B, max_blocks] int32
    (OOB sentinel N on unused slots); lens [B] current lengths INCLUDING
    the new token, whose row is already in the pool -> [B, H, v_width]."""
    b, h, w = q.shape
    n, bs, _ = pool.shape
    max_blocks = block_tables.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    per_step = blocks_per_step(bs, w, pool.dtype, max_blocks)
    row = lambda i, t, l: (i, 0, 0)  # noqa: E731
    kernel = functools.partial(
        _latent_decode_kernel, block_size=bs, scale=float(scale),
        max_blocks=max_blocks, per_step=per_step, v_width=v_width, n_pool=n)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, h, w), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, v_width), row),
            scratch_shapes=[pltpu.VMEM((2, per_step * bs, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, h, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        name="paged_latent_decode_attention",
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32),
      q.astype(pool.dtype), pool)


def paged_latent_decode_attention_xla(q, pool, block_tables, lens, *,
                                      v_width, scale):
    """Gather-based twin: the row's whole table width, dense and masked."""
    n, bs, w = pool.shape
    b, max_blocks = block_tables.shape
    rows = jnp.take(pool, jnp.minimum(block_tables, n - 1), axis=0)
    rows = rows.reshape(b, max_blocks * bs, w).astype(jnp.float32)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), rows) * scale
    keep = jnp.arange(max_blocks * bs)[None, None, :] < lens[:, None, None]
    p = jax.nn.softmax(jnp.where(keep, s, _NEG_INF), axis=-1)
    p = jnp.where(lens[:, None, None] > 0, p, 0.0)
    return jnp.einsum("bhk,bkv->bhv", p, rows[..., :v_width]).astype(q.dtype)


def paged_latent_decode_attention(q, pool, block_tables, lens, *, v_width,
                                  scale, interpret: bool | None = None):
    """Dispatch: the Pallas kernel on TPU for a pool whose slabs Mosaic can
    copy (``latent_slab_is_tiled``), the XLA gather elsewhere."""
    if mosaic_kernels_apply() and latent_slab_is_tiled(
            pool.shape[1], pool.shape[2], v_width, pool.dtype):
        _note_trace("latent_decode:pallas")
        return paged_latent_decode_attention_pallas(
            q, pool, block_tables, lens, v_width=v_width, scale=scale,
            interpret=interpret)
    _note_trace("latent_decode:xla")
    return paged_latent_decode_attention_xla(
        q, pool, block_tables, lens, v_width=v_width, scale=scale)


# ---------------------------------------------------------------- chunk
def chunk_q_tile(folded_rows: int) -> int:
    """Folded query rows (positions x heads) one grid step scores: whole
    128-lane rows of the transposed scores, no more than the chunk has."""
    return min(_CHUNK_Q_TILE_MAX, -(-folded_rows // 128) * 128)


def _latent_chunk_kernel(tables_ref, offs_ref, cls_ref, q_ref, pool_hbm,
                         o_ref, buf, sems, acc, m_scr, l_scr, *, block_size,
                         scale, max_blocks, per_step, group, v_width,
                         n_pool):
    """Grid (A, q tiles): one step scores ``q_tile`` folded query rows of
    one sequence against its pool prefix up to the tile's causal frontier.
    A tile past ``chunk_lens`` copies nothing, computes nothing and emits
    zeros. Scores transposed ``[T, q_tile]``; the accumulator is ``[v_width,
    q_tile]`` (``V^T P^T``), transposed back once when the tile is
    emitted."""
    i = pl.program_id(0)
    t = pl.program_id(1)
    qt = q_ref.shape[1]
    T = per_step * block_size
    off = offs_ref[i]
    live_rows = cls_ref[i] * group
    r0 = t * qt
    tile_live = r0 < live_rows

    @pl.when(jnp.logical_not(tile_live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(tile_live)
    def _():
        # the causal frontier: the block of the tile's last live query
        q_last = off + (jnp.minimum(r0 + qt, live_rows) - 1) // group
        n_live = q_last // block_size + 1
        c_hi = pl.cdiv(n_live, per_step)
        start, wait = _copies(tables_ref, i, pool_hbm, buf, sems,
                              block_size=block_size, per_step=per_step,
                              max_blocks=max_blocks, n_pool=n_pool)
        # rows no copy fills are masked in the scores and meet probability
        # 0 in the matmul with V: they must be finite
        buf[...] = jnp.zeros_like(buf)
        start(0, 0, n_live)           # a live tile has a block
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, qt), 1)
        qpos = off + (r0 + lane) // group                 # [1, qt]
        key = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        q = q_ref[0]                                      # [qt, W]

        def block(c, _):
            slot = c % 2

            @pl.when(c + 1 < c_hi)
            def _():
                start(c + 1, 1 - slot, n_live)

            wait(c, slot, n_live)
            k = buf[slot]                                 # [T, W]
            s = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            # causal, which keeps a live query inside the row's length too;
            # every query sees key 0, so its running max is finite from
            # the first block on and a masked score's exp is exactly 0
            s = jnp.where(c * T + key <= qpos, s, _NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            prob = jnp.exp(s - m_new)                     # [T, qt]
            l_scr[...] = l_scr[...] * corr + jnp.sum(prob, axis=0,
                                                     keepdims=True)
            m_scr[...] = m_new
            pv = jax.lax.dot_general(                     # V^T P^T
                k[:, :v_width], prob.astype(k.dtype),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc[...] = acc[...] * corr + pv               # [v_width, qt]

        jax.lax.fori_loop(0, c_hi, block, None)
        # folded rows past chunk_lens are padding and emit zeros
        real = (r0 + lane) < live_rows
        out = acc[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = jnp.where(real, out, 0.0).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("v_width", "scale", "q_tile",
                                             "interpret"))
def _latent_chunk_call(q, pool, block_tables, offsets, chunk_lens, *,
                       v_width, scale, q_tile, interpret):
    """The ``pallas_call`` and the folding around it under one ``jit`` of
    their own, so the layers of a program share one traced kernel."""
    a, c, h, w = q.shape
    n, bs, _ = pool.shape
    max_blocks = block_tables.shape[1]
    per_step = blocks_per_step(bs, w, pool.dtype, max_blocks)
    cg = c * h
    if q_tile is None:
        q_tile = chunk_q_tile(cg)
    n_qt = -(-cg // q_tile)
    qf = q.astype(pool.dtype).reshape(a, cg, w)   # row r: pos r // h
    if n_qt * q_tile != cg:
        qf = jnp.pad(qf, ((0, 0), (0, n_qt * q_tile - cg), (0, 0)))

    def q_tile_of(i, t, tables, offs, cls):
        # a dead tile names the block the steps before it named: the
        # pipeline fetches no queries for it
        live = t * q_tile < cls[i] * h
        return (jnp.where(live, i, 0), jnp.where(live, t, 0), 0)

    kernel = functools.partial(
        _latent_chunk_kernel, block_size=bs, scale=scale,
        max_blocks=max_blocks, per_step=per_step, group=h, v_width=v_width,
        n_pool=n)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(a, n_qt),
            in_specs=[pl.BlockSpec((1, q_tile, w), q_tile_of),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, q_tile, v_width),
                                   lambda i, t, *_: (i, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, per_step * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((v_width, q_tile), jnp.float32),
                pltpu.VMEM((1, q_tile), jnp.float32),
                pltpu.VMEM((1, q_tile), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((a, n_qt * q_tile, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL)),
        interpret=interpret,
        name="paged_latent_chunk_attention",
    )(block_tables.astype(jnp.int32), offsets.astype(jnp.int32),
      chunk_lens.astype(jnp.int32), qf, pool)
    return out[:, :cg].reshape(a, c, h, v_width)


def paged_latent_chunk_attention_pallas(q, pool, block_tables, offsets,
                                        chunk_lens, *, v_width, scale,
                                        q_tile=None,
                                        interpret: bool | None = None):
    """q [A, C, H, W] (rotated, absorbed) at positions ``offsets[a] ..
    offsets[a] + chunk_lens[a] - 1``; pool [N, bs, W] with the chunk's rows
    ALREADY scattered; causal over pool positions [0, offset + len) ->
    [A, C, H, v_width]. Rows with ``chunk_lens`` 0 are dead, and so are a
    live row's positions past its length (output 0)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_chunk_call(
        q, pool, jnp.asarray(block_tables), jnp.asarray(offsets),
        jnp.asarray(chunk_lens), v_width=int(v_width), scale=float(scale),
        q_tile=q_tile, interpret=bool(interpret))


def paged_latent_chunk_attention_xla(q, pool, block_tables, offsets,
                                     chunk_lens, *, v_width, scale):
    """Gather-based twin: each row's whole table width, dense and masked."""
    a, c, h, w = q.shape
    n, bs, _ = pool.shape
    max_blocks = block_tables.shape[1]
    offsets = jnp.asarray(offsets, jnp.int32)
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
    rows = jnp.take(pool, jnp.minimum(block_tables, n - 1), axis=0)
    rows = rows.reshape(a, max_blocks * bs, w).astype(jnp.float32)
    s = jnp.einsum("achw,akw->ahck", q.astype(jnp.float32), rows) * scale
    k_pos = jnp.arange(max_blocks * bs)[None, None, :]
    q_pos = (offsets[:, None] + jnp.arange(c, dtype=jnp.int32))[:, :, None]
    keep = k_pos <= q_pos                                 # [A, C, K]
    p = jax.nn.softmax(jnp.where(keep[:, None], s, _NEG_INF), axis=-1)
    live = jnp.arange(c)[None, :] < chunk_lens[:, None]   # [A, C]
    p = jnp.where(live[:, None, :, None], p, 0.0)
    return jnp.einsum("ahck,akv->achv", p,
                      rows[..., :v_width]).astype(q.dtype)


def paged_latent_chunk_attention(q, pool, block_tables, offsets, chunk_lens,
                                 *, v_width, scale,
                                 interpret: bool | None = None):
    """Dispatch for the ragged chunk path, as the decode dispatch."""
    if mosaic_kernels_apply() and latent_slab_is_tiled(
            pool.shape[1], pool.shape[2], v_width, pool.dtype):
        _note_trace("latent_chunk:pallas")
        return paged_latent_chunk_attention_pallas(
            q, pool, block_tables, offsets, chunk_lens, v_width=v_width,
            scale=scale, interpret=interpret)
    _note_trace("latent_chunk:xla")
    return paged_latent_chunk_attention_xla(
        q, pool, block_tables, offsets, chunk_lens, v_width=v_width,
        scale=scale)
