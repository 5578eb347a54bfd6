"""Paged attention over a LATENT cache (multi-head latent attention, MLA:
DeepSeek-V2/V3, Kimi-K2): the decode tick absorbed, a prefill call expanded.

A position's cache row in one layer is ``[c_kv | k_r]``: the normalised
compressed K/V latent (``kv_lora_rank`` values) and the one rotated key all
heads share (``qk_rope_head_dim`` values), side by side in ONE pool
``[num_blocks, block_size, W]`` (``latent_row_width``: the two widths
rounded up to whole 128-lane rows, the lanes past them zero). A head's
``k_nope | v`` is ``c_kv W_kvb``; absorbed into the query and the output,

    score_h(t, s) = q~_h(t) . row(s) * scale      q~_h = [q_nope_h W^K_h^T | q_rope_h]
    o_h(t)        = sum_s p_h(t, s) row(s)[:kv_lora_rank]

every head attends over the SAME rows, nothing expanded, at (2 x 512 + 64)
multiply-adds a (query, key) pair a head where the expanded form asks 192 +
128 and 2 x 512 x 256 once a key a head: 320 + 131,072 / c against 1,088
when c queries share the key, even at c = 171. One query a row is the
tick's; a chunk of thousands a prefill call's. The call site says which:

* **decode** (``paged_latent_decode_attention``, absorbed): q ``[B, H,
  W]``, grid ``(B,)``; a loop over the row's live compute blocks,
  ``per_step`` pool blocks a copy wave into one of two VMEM slots, scores
  ``[H, T]`` in one matmul, the values a lane slice of the same buffer.
* **chunk** (``paged_latent_chunk_attention``, expanded): q_nope, q_rope
  ``[A, C, H, .]`` at positions ``offsets[a] ..``, causal over the row's
  pool prefix; grid ``(A, head groups, q tiles)``, a head's slice of
  ``W_kvb`` resident in VMEM, each gathered block of rows expanded to that
  head's K and V by one MXU product and scored against a tile of
  POSITIONS, scores kept transposed ``[T, q_tile]``: no K or V in HBM.

Both read the pool where it lies (a ``[bs, W]`` slab is whole tiles at block
size 16), take bf16 operands into the MXU, keep the online softmax float32.
Dispatch is by backend and shape (``mosaic_kernels_apply``, ``*_is_tiled``);
off them the XLA twins run; tests: ``*_pallas(..., interpret=True)``.
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
_CHUNK_Q_TILE_MAX = 2048


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
# heads one grid step of the chunk kernel expands and scores, and the
# positions it scores at a time
_CHUNK_HEADS = 2
_CHUNK_SUB_TILE = 512


def chunk_q_tile(chunk: int, sub: int = _CHUNK_SUB_TILE) -> int:
    """Query POSITIONS one grid step holds: whole 128-lane rows of the
    transposed scores and whole sub-tiles, no more than the chunk has."""
    if chunk <= sub:
        return -(-chunk // 128) * 128
    return min(_CHUNK_Q_TILE_MAX, -(-chunk // sub) * sub)


def chunk_is_tiled(block_size, width, rank, nope, v_dim, dtype) -> bool:
    """Whether Mosaic can run the expanded chunk kernel: a pool whose slabs
    it copies, split at ``rank`` into whole 128-lane rows, and a head's
    ``[k_nope | v]`` columns of ``W_kvb`` whole 128-lane rows each."""
    return (latent_slab_is_tiled(block_size, width, rank, dtype)
            and rank < width and nope % 128 == 0 and v_dim % 128 == 0)


def _latent_chunk_kernel(tables_ref, offs_ref, cls_ref, q_ref, w_ref,
                         pool_hbm, o_ref, buf, sems, acc, m_scr, l_scr, *,
                         block_size, scale, max_blocks, per_step, rank,
                         nope, heads, sub, n_pool):
    """Grid (A, head groups, q tiles): one step scores ``q_tile`` positions
    of ``heads`` heads of one sequence against its pool prefix up to the
    tile's causal frontier. Each compute block of latent rows is expanded
    ONCE to the heads' K and V (``rows[:, :rank] @ W_kvb``, float32
    accumulate, rounded to the pool's dtype) and scored as ``[k_nope |
    k_r]`` against ``[q_nope | q_rope]``, ``sub`` positions at a time: a
    sub-tile past ``chunk_lens`` or wholly before the block is not scored,
    one wholly after it skips the causal mask. A tile past ``chunk_lens``
    copies nothing, computes nothing and emits zeros. Scores transposed
    ``[T, sub]``; a (head, sub-tile)'s accumulator is ``[v, sub]`` (``V^T
    P^T``), transposed back once when the tile is emitted."""
    i = pl.program_id(0)
    t = pl.program_id(2)
    qt = q_ref.shape[1]
    T = per_step * block_size
    n_sub = qt // sub
    dq, dkv = q_ref.shape[2] // heads, w_ref.shape[1] // heads
    v_dim = dkv - nope
    live = cls_ref[i]
    r0 = t * qt
    first = offs_ref[i] + r0          # the tile's first query's position

    @pl.when(r0 >= live)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(r0 < live)
    def _():
        n_q = jnp.minimum(qt, live - r0)                  # live queries
        subs = pl.cdiv(n_q, sub)                          # live sub-tiles
        # the causal frontier: the block of the tile's last live query
        n_live = (first + n_q - 1) // block_size + 1
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

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, sub), 1)
        key = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)

        def block(c, _):
            slot = c % 2

            @pl.when(c + 1 < c_hi)
            def _():
                start(c + 1, 1 - slot, n_live)

            wait(c, slot, n_live)
            rows = buf[slot]                              # [T, W]
            kv = jax.lax.dot_general(                     # [T, heads * dkv]
                rows[:, :rank], w_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(rows.dtype)
            ks = [jnp.concatenate(
                [kv[:, g * dkv:g * dkv + nope], rows[:, rank:]], axis=1)
                for g in range(heads)]                    # [T, dq] a head

            def score(j, _, *, masked):
                at = j * sub if isinstance(j, int) \
                    else pl.multiple_of(j * sub, sub)
                for g in range(heads):
                    r = g * n_sub + j
                    s = jax.lax.dot_general(
                        ks[g], q_ref[0, pl.ds(at, sub), g * dq:(g + 1) * dq],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    if masked:
                        # causal, which keeps a live query inside the row's
                        # length too; every query sees key 0, so its
                        # running max is finite from the first block on and
                        # a masked score's exp is exactly 0
                        s = jnp.where(c * T + key <= first + at + lane, s,
                                      _NEG_INF)
                    m_prev = m_scr[r]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, axis=0, keepdims=True))
                    corr = jnp.exp(m_prev - m_new)
                    prob = jnp.exp(s - m_new)             # [T, sub]
                    l_scr[r] = l_scr[r] * corr + jnp.sum(prob, axis=0,
                                                         keepdims=True)
                    m_scr[r] = m_new
                    pv = jax.lax.dot_general(             # V^T P^T
                        kv[:, g * dkv + nope:(g + 1) * dkv],
                        prob.astype(rows.dtype), (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    acc[r] = acc[r] * corr + pv           # [v, sub]

            # sub-tiles from the first whose last query sees the block's
            # first key; from the first whose first query sees its last
            # key on, nothing is masked
            ahead = c * T - first
            seen = jnp.maximum(ahead, 0) // sub
            clear = jnp.minimum(pl.cdiv(jnp.maximum(ahead + T - 1, 0), sub),
                                subs)
            # a block under a full tile's first query: every sub-tile,
            # unmasked, in one straight run the scheduler overlaps
            whole = jnp.logical_and(clear == 0, subs == n_sub)

            @pl.when(whole)
            def _():
                for j in range(n_sub):
                    score(j, None, masked=False)

            @pl.when(jnp.logical_not(whole))
            def _():
                jax.lax.fori_loop(seen, clear,
                                  functools.partial(score, masked=True),
                                  None)
                jax.lax.fori_loop(clear, subs,
                                  functools.partial(score, masked=False),
                                  None)

        jax.lax.fori_loop(0, c_hi, block, None)

        def emit(j, _):
            at = pl.multiple_of(j * sub, sub)
            # positions past chunk_lens are padding and emit zeros
            real = (r0 + at + lane) < live
            for g in range(heads):
                r = g * n_sub + j
                out = acc[r] / jnp.maximum(l_scr[r], 1e-30)
                o_ref[0, pl.ds(at, sub), g * v_dim:(g + 1) * v_dim] = \
                    jnp.where(real, out, 0.0).T.astype(o_ref.dtype)

        jax.lax.fori_loop(0, n_sub, emit, None)


@functools.partial(jax.jit, static_argnames=("scale", "q_tile", "sub_tile",
                                             "heads", "interpret"))
def _latent_chunk_call(q_nope, q_rope, w_kvb, pool, block_tables, offsets,
                       chunk_lens, *, scale, q_tile, sub_tile, heads,
                       interpret):
    """The ``pallas_call`` and the packing around it under one ``jit`` of
    their own, so the layers of a program share one traced kernel."""
    a, c, h, nope = q_nope.shape
    rank, _, dkv = w_kvb.shape
    n, bs, w = pool.shape
    max_blocks = block_tables.shape[1]
    per_step = blocks_per_step(bs, w, pool.dtype, max_blocks)
    if q_tile is None:
        q_tile = chunk_q_tile(c, sub_tile or _CHUNK_SUB_TILE)
    if heads is None:
        heads = _CHUNK_HEADS if h % _CHUNK_HEADS == 0 else 1
    sub = min(q_tile, sub_tile or _CHUNK_SUB_TILE)
    if q_tile % sub:
        raise ValueError(f"a q tile of {q_tile} positions is no whole "
                         f"number of sub-tiles of {sub}")
    n_qt = -(-c // q_tile)
    # a head's query against [k_nope | k_r | 0]: the pool row's lanes past
    # the latent, as they lie
    dq = nope + w - rank
    q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(pool.dtype)
    q = jnp.pad(q, ((0, 0), (0, n_qt * q_tile - c), (0, 0),
                    (0, dq - q.shape[-1]))).reshape(a, n_qt * q_tile, h * dq)

    def q_tile_of(i, g, t, tables, offs, cls):
        # a dead tile names the row's last live one (a dead row's, tile 0):
        # the pipeline fetches no queries for it
        return (i, jnp.minimum(t, jnp.maximum(pl.cdiv(cls[i], q_tile) - 1,
                                              0)), g)

    kernel = functools.partial(
        _latent_chunk_kernel, block_size=bs, scale=scale,
        max_blocks=max_blocks, per_step=per_step, rank=rank, nope=nope,
        heads=heads, sub=sub, n_pool=n)
    v_dim = dkv - nope
    rows = heads * q_tile // sub      # (head, sub-tile) pairs a grid step
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(a, h // heads, n_qt),
            in_specs=[pl.BlockSpec((1, q_tile, heads * dq), q_tile_of),
                      pl.BlockSpec((rank, heads * dkv),
                                   lambda i, g, t, *_: (0, g)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, q_tile, heads * v_dim),
                                   lambda i, g, t, *_: (i, t, g)),
            scratch_shapes=[
                pltpu.VMEM((2, per_step * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, v_dim, sub), jnp.float32),
                pltpu.VMEM((rows, 1, sub), jnp.float32),
                pltpu.VMEM((rows, 1, sub), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((a, n_qt * q_tile, h * v_dim),
                                       q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,) * 3),
        interpret=interpret,
        name="paged_latent_chunk_attention",
    )(block_tables.astype(jnp.int32), offsets.astype(jnp.int32),
      chunk_lens.astype(jnp.int32), q,
      w_kvb.astype(pool.dtype).reshape(rank, h * dkv), pool)
    return out[:, :c].reshape(a, c, h, v_dim)


def paged_latent_chunk_attention_pallas(q_nope, q_rope, w_kvb, pool,
                                        block_tables, offsets, chunk_lens,
                                        *, scale, q_tile=None, sub_tile=None,
                                        heads=None,
                                        interpret: bool | None = None):
    """The EXPANDED form over the latent pool. q_nope [A, C, H, nope] and
    the rotated q_rope [A, C, H, rope] at positions ``offsets[a] ..
    offsets[a] + chunk_lens[a] - 1``; w_kvb [rank, H, nope + v], a head's
    columns ``[k_nope | v]``; pool [N, bs, W] of rows ``[c_kv | k_r | 0]``
    with the chunk's rows ALREADY scattered; causal over pool positions
    [0, offset + len) -> [A, C, H, v]. Rows with ``chunk_lens`` 0 are dead,
    and so are a live row's positions past its length (output 0).
    ``q_tile`` positions and ``heads`` heads a grid step, ``sub_tile``
    positions scored at a time (a test's to force; the defaults are the
    kernel's own)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_chunk_call(
        q_nope, q_rope, w_kvb, pool, jnp.asarray(block_tables),
        jnp.asarray(offsets), jnp.asarray(chunk_lens), scale=float(scale),
        q_tile=q_tile, sub_tile=sub_tile, heads=heads,
        interpret=bool(interpret))


def paged_latent_chunk_attention_xla(q_nope, q_rope, w_kvb, pool,
                                     block_tables, offsets, chunk_lens, *,
                                     scale):
    """Gather-based twin: each row's whole table width expanded to K and V
    (rounded to the pool's dtype, as the kernel's), dense and masked."""
    a, c, h, nope = q_nope.shape
    rank, rope = w_kvb.shape[0], q_rope.shape[-1]
    n, bs, w = pool.shape
    max_blocks = block_tables.shape[1]
    offsets = jnp.asarray(offsets, jnp.int32)
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
    f32 = dict(preferred_element_type=jnp.float32)
    rows = jnp.take(pool, jnp.minimum(block_tables, n - 1), axis=0)
    rows = rows.reshape(a, max_blocks * bs, w)
    kv = jnp.einsum("akc,chd->akhd", rows[..., :rank],
                    w_kvb.astype(pool.dtype), **f32).astype(pool.dtype)
    s = (jnp.einsum("achd,akhd->ahck", q_nope.astype(pool.dtype),
                    kv[..., :nope], **f32)
         + jnp.einsum("achd,akd->ahck", q_rope.astype(pool.dtype),
                      rows[..., rank:rank + rope], **f32)) * scale
    k_pos = jnp.arange(max_blocks * bs)[None, None, :]
    q_pos = (offsets[:, None] + jnp.arange(c, dtype=jnp.int32))[:, :, None]
    keep = k_pos <= q_pos                                 # [A, C, K]
    p = jax.nn.softmax(jnp.where(keep[:, None], s, _NEG_INF), axis=-1)
    live = jnp.arange(c)[None, :] < chunk_lens[:, None]   # [A, C]
    p = jnp.where(live[:, None, :, None], p, 0.0)
    return jnp.einsum("ahck,akhd->achd", p.astype(pool.dtype),
                      kv[..., nope:], **f32).astype(q_nope.dtype)


def paged_latent_chunk_attention(q_nope, q_rope, w_kvb, pool, block_tables,
                                 offsets, chunk_lens, *, scale,
                                 interpret: bool | None = None):
    """Dispatch for a prefill call's ragged chunk, by backend and shape as
    the decode dispatch: the expanded kernel, or its gather twin."""
    nope = q_nope.shape[-1]
    if mosaic_kernels_apply() and chunk_is_tiled(
            pool.shape[1], pool.shape[2], w_kvb.shape[0], nope,
            w_kvb.shape[-1] - nope, pool.dtype):
        _note_trace("latent_chunk:expanded")
        return paged_latent_chunk_attention_pallas(
            q_nope, q_rope, w_kvb, pool, block_tables, offsets, chunk_lens,
            scale=scale, interpret=interpret)
    _note_trace("latent_chunk:expanded_xla")
    return paged_latent_chunk_attention_xla(
        q_nope, q_rope, w_kvb, pool, block_tables, offsets, chunk_lens,
        scale=scale)
