"""Paged-attention kernels (ref capability: PaddleNLP ``llm``
block-attention / ``paddle/phi/kernels/fusion/gpu/
fused_multi_transformer_op.cu`` block KV cache).

TPU-first design: the KV cache is a POOL of fixed-size blocks
([num_blocks, block_size, H_kv, D]) shared by all sequences; each sequence
owns a row of ``block_tables`` (pool indices). Attention reads a
sequence's blocks pool-directly through a scalar-prefetched block table
(``pltpu.PrefetchScalarGridSpec``), so the gathered K/V is NEVER
materialised: HBM holds pool ≈ Σ actual lengths (not B × max_len).

Two kernels share that scheme:

* **decode** — q [B, H, D] (one token per sequence), grid (B,): one step
  per sequence. The pools stay in HBM as stored; the kernel walks the
  row's LIVE blocks only (lens [B], the window, cp ownership), copying
  ``decode_blocks_per_step`` ``[bs, H_kv, D]`` slabs at a time (every K/V
  head of a block in one copy, so GQA fetches nothing twice) into two
  VMEM slots a pool, the next compute block in flight under this one's
  arithmetic. VMEM holds four such buffers (2 MB in all) and the
  sequence's ``[H, D]`` queries. Mosaic copies a slab only as whole
  tiles of the pool's layout (``decode_slab_is_tiled``: D in 128 lanes,
  H_kv in whole sublane tiles, which every 128-wide GQA/MHA family
  meets); head_dim 64 and bf16/int8 MQA pools take the XLA gather.
* **chunk** (ISSUE 11) — the ragged MULTI-query forward behind chunked
  prefill and the spec-decode ``(slots, k+1)`` verify batch: q
  [A, C, H, D] chunk queries at positions ``offsets[a] ..
  offsets[a]+chunk_lens[a]-1``, attending causally over the slot's whole
  pool prefix. Grid (A*H_kv, q-tile, kv-block), one ``[bs, D]`` tile of a
  head-major copy of the pool a step; the H/H_kv query heads of a KV head
  fold into the q tile, so GQA needs no repeated K/V.

Unused table slots hold the OOB sentinel (= num_blocks): the decode
kernel never reads them, the chunk kernel's index maps clamp them and the
length scalars mask the compute off.

Dispatch functions (``paged_decode_attention`` /
``paged_chunk_attention``) pick Pallas on TPU and the XLA gather
reference elsewhere, from the backend and the shapes alone. On TPU a
kernel that fails to trace or lower raises: there is no downgrade to the
gather path. ``PT_PAGED_CHUNK=0`` force-kills the chunk kernel
(``=interpret`` forces the interpreted kernel off-TPU, the engine-level
parity mode).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import mosaic_kernels_apply

_NEG_INF = -1e30

# trace-time breadcrumbs ("chunk:xla-forced", "chunk:pallas", ...): one
# entry per DISPATCH TRACE, so tests can assert which implementation a
# jitted program actually baked in (flipping PT_PAGED_CHUNK without
# clearing jit caches appends nothing — the stale trace is reused)
_trace_events: list[str] = []


def _note_trace(event: str):
    if len(_trace_events) >= 512:
        del _trace_events[:256]
    _trace_events.append(event)


# VMEM one K (or V) buffer of the decode kernel may fill; the kernel keeps
# four (K and V, two slots each). The number of pool blocks a compute
# block gathers follows from it and from the shape of a block.
_DECODE_BUFFER_BYTES = 512 * 1024


def decode_blocks_per_step(block_size, h_kv, d, dtype, max_blocks):
    """Pool blocks the decode kernel gathers and scores at once: as many
    ``[block_size, H_kv, D]`` slabs as fit ``_DECODE_BUFFER_BYTES``, at
    least one, at most the table's width."""
    slab = block_size * h_kv * d * jnp.dtype(dtype).itemsize
    return int(max(1, min(_DECODE_BUFFER_BYTES // slab, max_blocks)))


def decode_slab_is_tiled(h_kv, d, dtype):
    """Whether Mosaic can copy a ``[bs, H_kv, D]`` slab of the pool. A
    copy moves whole tiles of the last two dims: D has to fill 128-lane
    rows, and H_kv the sublane tile Mosaic gives the dtype (the rows one
    32-bit sublane packs: 1 f32, 2 bf16, 4 int8; doubled up to H_kv or
    8). Off it (head_dim 64, bf16 or int8 MQA) the slice is refused:
    ``tests/test_tpu_compile.py`` compiles both sides of this rule."""
    tile = 4 // jnp.dtype(dtype).itemsize
    while tile < min(h_kv, 8):
        tile *= 2
    return d % 128 == 0 and h_kv % tile == 0


def _paged_decode_kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, *rest,
                         block_size, scale, max_blocks, per_step, kv_rep,
                         window, quantized, partials, n_pool):
    """Grid (B,): one step per sequence, the whole ``[H, D]`` query tile
    in VMEM. The pools stay in HBM as stored (``[N, bs, H_kv, D]``); a
    loop over the row's live compute blocks gathers ``per_step`` table
    entries each (one ``[bs, H_kv, D]`` slab per entry and pool, every
    K/V head in it) into one of two VMEM slots, the next compute block's
    copies in flight under this one's arithmetic. Blocks past the live
    length, below the window, or (``partials``) owned by another shard
    are neither fetched nor walked.

    A compute block is scored in one matmul over its ``[T*H_kv, D]`` rows
    (T tokens x every K/V head, the slab order): row h of the
    ``[H, T*H_kv]`` scores keeps the columns of its own K/V head
    (h // kv_rep), the mask drops the rest with the ragged tail. Online
    softmax state (m, l, acc) is float32 and carried by the loop.
    ``quantized`` (static): int8 pools, the per-(position, head) scales
    arrive gathered along the table and multiply the scores (K) and the
    probabilities (V) column-wise. ``partials`` (static): emit the raw
    (acc, m, l) triple for the cross-shard merge."""
    if quantized:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    if partials:
        o_ref, m_ref, l_ref, kbuf, vbuf, sems = rest
    else:
        o_ref, kbuf, vbuf, sems = rest
    b = pl.program_id(0)
    bs, P = block_size, per_step
    h, d = q_ref.shape[1:]
    h_kv = h // kv_rep
    cols = P * bs * h_kv              # K/V rows of one compute block

    seq_len = lens_ref[b]
    n_live = pl.cdiv(seq_len, bs)
    first = 0
    if window is not None:
        # blocks entirely below seq_len - window are invisible
        first = jnp.maximum(seq_len - window, 0) // bs
    c_lo = first // P
    c_hi = pl.cdiv(n_live, P)

    def entry(j):
        return tables_ref[b, jnp.minimum(j, max_blocks - 1)]

    def each_copy(c, slot, act):
        """``act`` on the K and the V copy of every entry of compute
        block c that is fetched (a scalar loop: one copy's code)."""
        def one(p, _):
            j = c * P + p
            go = (j >= first) & (j < n_live)
            if partials:
                go &= entry(j) < n_pool
            blk = jnp.minimum(entry(j), n_pool - 1)
            rows = pl.ds(p * bs, bs)

            @pl.when(go)
            def _():
                act(pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot, rows],
                                          sems.at[0, slot]))
                act(pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot, rows],
                                          sems.at[1, slot]))
        jax.lax.fori_loop(0, P, one, None)

    def start(c, slot):
        each_copy(c, slot, lambda copy: copy.start())

    def wait(c, slot):
        each_copy(c, slot, lambda copy: copy.wait())

    # rows no copy fills (the ragged tail of the last compute block) meet
    # probability 0 in the P.V matmul: they must be finite, and fresh
    # VMEM need not be
    vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(c_lo < c_hi)
    def _():
        start(c_lo, c_lo % 2)

    q = q_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    tok = col // h_kv                 # token of a column, in the block
    tok_block = tok // bs             # and which of the P entries holds it
    own_head = (jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1) % h_kv
                == jax.lax.broadcasted_iota(jnp.int32, (h, cols), 0)
                // kv_rep)

    def body(c, carry):
        m_prev, l_prev, acc = carry
        slot = c % 2

        @pl.when(c + 1 < c_hi)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        k = kbuf[slot]
        v = vbuf[slot].astype(jnp.float32).reshape(cols, d)
        if k.dtype != q.dtype:
            k = k.astype(jnp.float32)
        # [H, D] x [T*H_kv, D]^T: bf16 operands keep every product exact
        # in the float32 accumulator
        s = jax.lax.dot_general(q.astype(k.dtype), k.reshape(cols, d),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * ks_ref[0, pl.ds(c, 1), :]
        pos = c * (P * bs) + tok
        keep = pos < seq_len
        if window is not None:
            keep &= pos >= seq_len - window
        if partials:
            # columns of entries this shard does not own: not fetched,
            # the slot still holds an older block there
            owned = jnp.zeros_like(keep)
            for p in range(P):      # unrolled: a loop cannot carry a mask
                owned |= (tok_block == p) & (entry(c * P + p) < n_pool)
            keep &= owned
        keep = own_head & keep
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # a row with no visible column here (partials) has m_new ==
        # _NEG_INF and exp(0) == 1 everywhere: the select zeroes it
        prob = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * corr + jnp.sum(prob, axis=1, keepdims=True)
        if quantized:
            prob = prob * vs_ref[0, pl.ds(c, 1), :]
        pv = jax.lax.dot_general(prob, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    m, l, acc = jax.lax.fori_loop(
        c_lo, c_hi, body,
        (jnp.full((h, 1), _NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, d), jnp.float32)))
    if partials:
        # m/l lane-replicated: a [H, 1] store is a masked one
        o_ref[0] = acc
        m_ref[0] = jnp.broadcast_to(m, (h, 128))
        l_ref[0] = jnp.broadcast_to(l, (h, 128))
    else:
        # a row of length 0 walked nothing: l == 0, emit 0, not NaN
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, lens, *,
                                  scale=None, window=None, k_scale=None,
                                  v_scale=None, partials=False,
                                  interpret: bool | None = None):
    """One decode step over block tables. q: [B, H, D];
    k_pool/v_pool: [N, bs, H_kv, D]; block_tables: [B, max_blocks] int32;
    lens: [B] int32 (current lengths INCLUDING the new token, whose K/V
    must already be written to the pool). ``k_scale``/``v_scale``
    [N, bs, H_kv] f32 dequantize an int8 pool in-kernel (per-position,
    per-head absmax scales). Returns [B, H, D] — or, with
    ``partials=True`` (context parallelism), the un-normalised
    online-softmax triple (acc [B, H, D] f32, m [B, H] f32, l [B, H]
    f32) over the table entries < N only (non-owned entries hold the
    OOB sentinel and are skipped).

    The pools are handed to the kernel in HBM as they are stored: no
    transpose, no pool-sized temporary. VMEM holds two slots of
    ``decode_blocks_per_step`` blocks for K and for V. Compiled
    (``interpret=False``) the shape has to meet
    ``decode_slab_is_tiled``."""
    b, h, d = q.shape
    n, bs, h_kv, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    quantized = k_scale is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    per_step = decode_blocks_per_step(bs, h_kv, d, k_pool.dtype, max_blocks)
    cols = per_step * bs * h_kv
    tables = block_tables.astype(jnp.int32)

    row = lambda i, t, l: (i, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, h, d), row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, k_pool, v_pool]
    if quantized:
        # the scales are small (4 bytes a position and head): gathered
        # along the table here, a row of compute blocks a sequence
        n_steps = -(-max_blocks // per_step)
        clamped = jnp.minimum(tables, n - 1)

        def along_table(pool):
            g = jnp.take(pool, clamped, axis=0).reshape(b, max_blocks, -1)
            g = jnp.pad(g, ((0, 0), (0, n_steps * per_step - max_blocks),
                            (0, 0)))
            return g.reshape(b, n_steps, cols)

        in_specs += [pl.BlockSpec((1, n_steps, cols), row)] * 2
        operands += [along_table(k_scale), along_table(v_scale)]

    out_specs = pl.BlockSpec((1, h, d), row)
    out_shape = jax.ShapeDtypeStruct((b, h, d), q.dtype)
    if partials:
        # acc in f32 (the merge renormalises before the dtype cast) plus
        # lane-replicated m/l rows
        out_specs = [out_specs, pl.BlockSpec((1, h, 128), row),
                     pl.BlockSpec((1, h, 128), row)]
        out_shape = [jax.ShapeDtypeStruct((b, h, d), jnp.float32),
                     jax.ShapeDtypeStruct((b, h, 128), jnp.float32),
                     jax.ShapeDtypeStruct((b, h, 128), jnp.float32)]
    slots = (2, per_step * bs, h_kv, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(slots, k_pool.dtype),
                        pltpu.VMEM(slots, v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    kernel = functools.partial(_paged_decode_kernel, block_size=bs,
                               scale=scale, max_blocks=max_blocks,
                               per_step=per_step, kv_rep=h // h_kv,
                               window=window, quantized=quantized,
                               partials=partials, n_pool=n)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # sequences are independent: every step sets up its own state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        # the kernel is handed over as a functools.partial, which has no
        # __name__: without this the custom call prints under the name of
        # the jitted program around it
        name="paged_decode_attention",
    )(tables, lens.astype(jnp.int32), *operands)
    if partials:
        acc, m, l = out
        return acc, m[..., 0], l[..., 0]
    return out


def paged_decode_attention_xla(q, k_pool, v_pool, block_tables, lens, *,
                               scale=None, window=None, k_scale=None,
                               v_scale=None, partials=False):
    """Gather-based reference path (CPU tests / fallback). Same contract as
    the Pallas kernel; materialises the gathered K/V transiently.
    ``partials=True`` returns the (acc, m, l) triple over owned table
    entries only — bit-compatible with the Pallas partials mode."""
    b, h, d = q.shape
    n, bs, h_kv, _ = k_pool.shape
    scale = scale if scale is not None else d ** -0.5
    max_blocks = block_tables.shape[1]
    # clamp the OOB padding sentinel (= num_blocks): jnp.take's fill mode
    # would yield NaN rows, which the length mask cannot launder
    tables = jnp.minimum(block_tables, n - 1)
    k = jnp.take(k_pool, tables, axis=0)  # [B, MB, bs, H_kv, D]
    v = jnp.take(v_pool, tables, axis=0)
    if k_scale is not None:
        # int8 pool: gather the scale rows the same way and dequantize in
        # f32 (never downcast — the attention math below is f32 anyway)
        k = k.astype(jnp.float32) * jnp.take(k_scale, tables,
                                             axis=0)[..., None]
        v = v.astype(jnp.float32) * jnp.take(v_scale, tables,
                                             axis=0)[..., None]
    k = k.reshape(b, max_blocks * bs, h_kv, d)
    v = v.reshape(b, max_blocks * bs, h_kv, d)
    if h_kv != h:
        rep = h // h_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_blocks * bs)[None, None, :]
    keep = pos < lens[:, None, None]
    if window is not None:
        keep &= pos >= (lens[:, None, None] - window)
    if partials:
        # ownership mask (cp): a clamped non-owned sentinel slot would
        # otherwise contribute a garbage block the position mask cannot
        # catch — only entries < N are this shard's
        keep = keep & jnp.repeat(block_tables < n, bs,
                                 axis=1)[:, None, :]
        s = jnp.where(keep, s, _NEG_INF)
        m = jnp.max(s, axis=-1)                       # [B, H]
        # the explicit keep multiply kills the all-masked degenerate row
        # (m == -1e30 -> exp(0) == 1 everywhere without it)
        p = jnp.exp(s - m[..., None]) * keep
        acc = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32))
        return acc, m, jnp.sum(p, axis=-1)
    s = jnp.where(keep, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lens, *,
                           scale=None, window=None, k_scale=None,
                           v_scale=None, partials=False,
                           interpret: bool | None = None):
    """Dispatch: Pallas on TPU (pool-direct block reads) for pools whose
    slabs Mosaic can copy (``decode_slab_is_tiled``), XLA elsewhere.
    ``window``: sliding-window bound — only the last `window` positions
    are visible (Mistral decode semantics). ``k_scale``/``v_scale``
    [N, bs, H_kv] f32 mark an int8 pool — dequantize-on-read in both
    paths. ``partials=True`` (context parallelism) returns the raw
    (acc, m, l) online-softmax triple over OWNED table entries only
    (< N; non-owned entries hold the OOB sentinel) — the caller merges
    across shards. On TPU a Pallas failure raises."""
    if k_scale is not None:
        # breadcrumb ONLY on the quantized branch, so bf16 traces stay
        # byte-identical to pre-quantization builds
        _note_trace("decode:int8-kv")
    if partials:
        _note_trace("decode:partials")
    if mosaic_kernels_apply():
        if decode_slab_is_tiled(*k_pool.shape[2:], k_pool.dtype):
            out = paged_decode_attention_pallas(
                q, k_pool, v_pool, block_tables, lens, scale=scale,
                window=window, k_scale=k_scale, v_scale=v_scale,
                partials=partials, interpret=interpret)
            _note_trace("decode:pallas")
            return out
        _note_trace("decode:slab-off-tiling")
    _note_trace("decode:xla")
    return paged_decode_attention_xla(q, k_pool, v_pool, block_tables, lens,
                                      scale=scale, window=window,
                                      k_scale=k_scale, v_scale=v_scale,
                                      partials=partials)


# --------------------------------------------------------- chunk kernel
# The ragged multi-query forward (ISSUE 11): chunked prefill writes C
# tokens per row at offsets[a]..offsets[a]+chunk_lens[a]-1 and each of
# them attends causally over the row's WHOLE pool prefix. The spec-decode
# verify batch is the same program at C = k+1. The q tile folds the
# H/H_kv query heads of one KV head (GQA without repeating K/V), and the
# kv-block axis walks the row's block table with dead tiles skipped:
# blocks past the causal frontier of a q tile (and past the row's live
# length) clamp their index map to the last live block, so Mosaic never
# issues a fresh DMA for them, and their compute is @pl.when-masked.

def _paged_chunk_kernel(tables_ref, offs_ref, cls_ref, q_ref, k_ref, v_ref,
                        *rest, block_size, scale, max_blocks, q_tile,
                        group, n_kv, window, quantized, partials,
                        n_pool=0):
    """Grid (A*H_kv, q-tiles, kv-blocks). Row r serves sequence
    a = r // n_kv, KV head r % n_kv; its q tile holds ``q_tile`` folded
    rows (folded row t = query position t // group, grouped head
    t % group). Online-softmax accumulation across the kv-block axis.
    ``quantized`` (static) adds two per-position scale refs after v_ref
    (int8 pool, dequantize in-kernel). ``partials`` (static, context
    parallelism): emit the raw (acc, m, l) triple instead of the
    normalised output and skip non-owned table entries (translated to
    the OOB sentinel by the caller)."""
    if quantized:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    if partials:
        o_ref, m_ref, l_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    r = pl.program_id(0)
    qt = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    a_idx = r // n_kv
    off = offs_ref[a_idx, 0]
    cl = cls_ref[a_idx, 0]
    row_len = off + cl                     # this row's live pool length
    n_live = pl.cdiv(row_len, block_size)
    q0 = qt * q_tile                       # first folded row of the tile
    last_q = off + (q0 + q_tile - 1) // group   # tile's last query position
    live = (j < n_live) & (q0 < cl * group)
    if partials:
        # ownership mask: non-owned table entries were translated to the
        # local sentinel — the owning shard's partial covers them
        live &= tables_ref[a_idx, j] < n_pool
    # causal dead-tile skip: a block whose FIRST key position is past the
    # tile's LAST query position contributes nothing
    live &= j * block_size <= last_q
    if window is not None:
        # sliding window: a block entirely below the tile's first query's
        # window is invisible to every query in the tile
        first_q = off + q0 // group
        live &= (j + 1) * block_size - 1 > first_q - window

    @pl.when(live)
    def _compute():
        q = q_ref[0]                       # [q_tile, D] folded queries
        k = k_ref[0, 0].astype(jnp.float32)    # [block_size, D]
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0]           # [block_size, 1] over D
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(q.astype(jnp.float32), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        row_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = off + (q0 + row_t) // group
        kpos = j * block_size + col
        # causal + ragged: key visible iff it is at/before the query AND
        # inside the row's live length; folded rows past chunk_lens*group
        # are padding (their tile output is discarded by the caller)
        keep = (kpos <= qpos) & (kpos < row_len)
        keep &= (q0 + row_t) < cl * group
        if window is not None:
            keep &= (qpos - kpos) < window
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if partials:
            # a row whose visible keys ALL live on other shards is fully
            # masked here: m_new == _NEG_INF and exp(s - m_new) == 1 —
            # the explicit keep multiply zeroes it so the merged triple
            # stays (acc=0, l=0) instead of garbage (cp=1 never hits
            # this: block 0 always holds visible keys for a real row)
            p = p * keep.astype(jnp.float32)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        pv = jax.lax.dot_general(p, v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr + pv

    @pl.when(j == max_blocks - 1)
    def _finalize():
        if partials:
            o_ref[0] = acc[:].astype(o_ref.dtype)
            m_ref[0] = m_scr[:]
            l_ref[0] = l_scr[:]
        else:
            # fully-masked rows (dead/padding) have l == 0: emit 0, not NaN
            o_ref[0] = (acc[:] / jnp.maximum(l_scr[:, :1], 1e-30)
                        ).astype(o_ref.dtype)


def paged_chunk_attention_pallas(q, k_pool, v_pool, block_tables, offsets,
                                 chunk_lens, *, scale=None, window=None,
                                 k_scale=None, v_scale=None, q_tile=None,
                                 partials=False,
                                 interpret: bool | None = None):
    """Ragged chunk attention over block tables. q: [A, C, H, D] (chunk
    queries, already rotated); k_pool/v_pool: [N, bs, H_kv, D] with the
    chunk K/V ALREADY scattered pool-side; block_tables: [A, max_blocks]
    int32 (OOB sentinel = N on unused slots); offsets/chunk_lens: [A]
    int32 — row a's queries sit at positions offsets[a] ..
    offsets[a]+chunk_lens[a]-1 and attend over pool positions
    [0, offsets[a]+chunk_lens[a]) causally. Rows with chunk_lens == 0 are
    dead (output 0). Returns [A, C, H, D] — or, with ``partials=True``
    (context parallelism), the raw (acc [A, C, H, D] f32, m [A, C, H]
    f32, l [A, C, H] f32) triple over owned table entries only."""
    a, c, h, d = q.shape
    n, bs, h_kv, _ = k_pool.shape
    group = h // h_kv
    max_blocks = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    quantized = k_scale is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    cg = c * group
    if q_tile is None:
        # sublane-aligned tile; one tile unless the folded chunk is large
        q_tile = min(256, -(-cg // 8) * 8)
    n_qt = -(-cg // q_tile)
    pad = n_qt * q_tile - cg

    # fold the grouped query heads into the row axis: row t of (a, kv) is
    # query position t // group, grouped head t % group — matches the
    # (head // kv_rep) GQA convention of the decode kernel
    qf = q.reshape(a, c, h_kv, group, d).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(a * h_kv, cg, d)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))

    tables = jnp.asarray(block_tables, jnp.int32)
    offs = jnp.asarray(offsets, jnp.int32)[:, None]
    cls = jnp.asarray(chunk_lens, jnp.int32)[:, None]

    kp = jnp.moveaxis(k_pool, 2, 0)        # [H_kv, N, bs, D]
    vp = jnp.moveaxis(v_pool, 2, 0)

    def q_index(r, qt, j, tables, offs, cls):
        return (r, qt, 0)

    def kv_index(r, qt, j, tables, offs, cls):
        a_i = r // n_kv_s
        row_len = offs[a_i, 0] + cls[a_i, 0]
        n_live = (row_len + bs - 1) // bs
        last_q = offs[a_i, 0] + (qt * q_tile + q_tile - 1) // group
        # dead trailing steps (past the causal frontier or the live
        # length) revisit the last live block: same index -> no new DMA
        hi = jnp.minimum(n_live - 1, last_q // bs)
        jl = jnp.minimum(j, jnp.maximum(hi, 0))
        return (r % n_kv_s, jnp.minimum(tables[a_i, jl], n - 1), 0, 0)

    n_kv_s = h_kv
    in_specs = [
        pl.BlockSpec((1, q_tile, d), q_index),
        pl.BlockSpec((1, 1, bs, d), kv_index),
        pl.BlockSpec((1, 1, bs, d), kv_index),
    ]
    operands = [qf, kp, vp]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, bs, 1), kv_index),
                     pl.BlockSpec((1, 1, bs, 1), kv_index)]
        operands += [jnp.moveaxis(k_scale, 2, 0)[..., None],
                     jnp.moveaxis(v_scale, 2, 0)[..., None]]
    out_specs = pl.BlockSpec((1, q_tile, d), q_index)
    out_shape = jax.ShapeDtypeStruct((a * h_kv, n_qt * q_tile, d), q.dtype)
    if partials:
        out_specs = [out_specs,
                     pl.BlockSpec((1, q_tile, 128), q_index),
                     pl.BlockSpec((1, q_tile, 128), q_index)]
        out_shape = [
            jax.ShapeDtypeStruct((a * h_kv, n_qt * q_tile, d), jnp.float32),
            jax.ShapeDtypeStruct((a * h_kv, n_qt * q_tile, 128),
                                 jnp.float32),
            jax.ShapeDtypeStruct((a * h_kv, n_qt * q_tile, 128),
                                 jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(a * h_kv, n_qt, max_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((q_tile, d), jnp.float32),
            # per-folded-row running max / denom, lane-replicated (scalar
            # (x, 1) VMEM stores hit Mosaic layout restrictions)
            pltpu.VMEM((q_tile, 128), jnp.float32),
            pltpu.VMEM((q_tile, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_chunk_kernel, block_size=bs,
                               scale=scale, max_blocks=max_blocks,
                               q_tile=q_tile, group=group, n_kv=h_kv,
                               window=window, quantized=quantized,
                               partials=partials, n_pool=n)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # rows and q tiles are independent; only the kv-block axis carries
        # the online-softmax state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name="paged_chunk_attention",
    )(tables, offs, cls, *operands)

    def unfold(x, last):
        x = x[:, :cg].reshape(a, h_kv, c, group, *((last,) if last else ()))
        if last:
            return x.transpose(0, 2, 1, 3, 4).reshape(a, c, h, last)
        return x.transpose(0, 2, 1, 3).reshape(a, c, h)

    if partials:
        acc, m, l = out
        return unfold(acc, d), unfold(m[..., 0], 0), unfold(l[..., 0], 0)
    return unfold(out, d)


def paged_chunk_attention_xla(q, k_pool, v_pool, block_tables, offsets,
                              chunk_lens, *, scale=None, window=None,
                              k_scale=None, v_scale=None, partials=False):
    """Gather-based reference path (CPU / fallback): materialise each
    row's whole ``max_blocks*bs`` pool view and run dense masked
    attention — exactly the pre-kernel ``llama_prefill_chunk_paged``
    inner loop, kept bit-compatible for the PT_PAGED_CHUNK=0 kill
    switch. ``partials=True`` returns the (acc, m, l) triple over owned
    table entries only (context parallelism)."""
    from paddle_tpu.ops import attention as A
    a, c, h, d = q.shape
    n, bs, h_kv, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    offsets = jnp.asarray(offsets, jnp.int32)
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
    tbl = jnp.minimum(block_tables, n - 1)
    kg = jnp.take(k_pool, tbl, axis=0)
    vg = jnp.take(v_pool, tbl, axis=0)
    if k_scale is not None:
        kg = kg.astype(jnp.float32) * jnp.take(k_scale, tbl,
                                               axis=0)[..., None]
        vg = vg.astype(jnp.float32) * jnp.take(v_scale, tbl,
                                               axis=0)[..., None]
    kg = kg.reshape(a, max_blocks * bs, h_kv, d)
    vg = vg.reshape(a, max_blocks * bs, h_kv, d)
    pool_pos = jnp.arange(max_blocks * bs)[None, None, :]
    q_pos = (offsets[:, None]
             + jnp.arange(c, dtype=jnp.int32))[:, :, None]
    row_lens = offsets + chunk_lens
    keep = (pool_pos <= q_pos) & (pool_pos < row_lens[:, None, None])
    if window is not None:
        keep &= (q_pos - pool_pos) < window
    if partials:
        # ownership mask (cp): clamped non-owned sentinel slots must not
        # contribute — the owning shard's partial covers those positions
        keep = keep & jnp.repeat(block_tables < n, bs,
                                 axis=1)[:, None, :]   # [A, C, K]
        if h_kv != h:
            kg = jnp.repeat(kg, h // h_kv, axis=2)
            vg = jnp.repeat(vg, h // h_kv, axis=2)
        scale_ = scale if scale is not None else d ** -0.5
        s = jnp.einsum("achd,akhd->ahck", q.astype(jnp.float32),
                       kg.astype(jnp.float32)) * scale_
        km = keep[:, None].astype(bool)                # [A, 1, C, K]
        s = jnp.where(km, s, _NEG_INF)
        m = jnp.max(s, axis=-1)                        # [A, H, C]
        p = jnp.exp(s - m[..., None]) * km             # kill all-masked rows
        acc = jnp.einsum("ahck,akhd->achd", p, vg.astype(jnp.float32))
        return (acc, jnp.moveaxis(m, 1, 2),            # [A, C, H]
                jnp.moveaxis(jnp.sum(p, axis=-1), 1, 2))
    return A.xla_attention(q, kg, vg, attn_mask=keep[:, None], scale=scale)


def paged_chunk_attention(q, k_pool, v_pool, block_tables, offsets,
                          chunk_lens, *, scale=None, window=None,
                          k_scale=None, v_scale=None, partials=False,
                          interpret: bool | None = None):
    """One dispatch for the ragged chunk path. ``PT_PAGED_CHUNK``
    (read at TRACE time — flip it between engine constructions together
    with ``models.paged.clear_jit_caches``):

      unset/1     Pallas kernel on TPU, XLA gather elsewhere (default)
      0/off/xla   force the XLA gather path (kill switch)
      interpret   force the interpreted Pallas kernel (off-TPU parity)

    ``k_scale``/``v_scale`` [N, bs, H_kv] f32 mark an int8 pool —
    dequantize-on-read in every implementation. Like the decode
    dispatch, a Pallas failure on TPU raises."""
    if k_scale is not None:
        _note_trace("chunk:int8-kv")
    if partials:
        _note_trace("chunk:partials")
    mode = os.environ.get("PT_PAGED_CHUNK", "1").strip().lower()
    if mode in ("0", "off", "xla"):
        _note_trace("chunk:xla-forced")
        return paged_chunk_attention_xla(
            q, k_pool, v_pool, block_tables, offsets, chunk_lens,
            scale=scale, window=window, k_scale=k_scale, v_scale=v_scale,
            partials=partials)
    if mode == "interpret":
        _note_trace("chunk:pallas-interpret")
        return paged_chunk_attention_pallas(
            q, k_pool, v_pool, block_tables, offsets, chunk_lens,
            scale=scale, window=window, k_scale=k_scale, v_scale=v_scale,
            partials=partials, interpret=True)
    if mosaic_kernels_apply():
        out = paged_chunk_attention_pallas(
            q, k_pool, v_pool, block_tables, offsets, chunk_lens,
            scale=scale, window=window, k_scale=k_scale,
            v_scale=v_scale, partials=partials, interpret=interpret)
        _note_trace("chunk:pallas")
        return out
    _note_trace("chunk:xla")
    return paged_chunk_attention_xla(
        q, k_pool, v_pool, block_tables, offsets, chunk_lens,
        scale=scale, window=window, k_scale=k_scale, v_scale=v_scale,
        partials=partials)
