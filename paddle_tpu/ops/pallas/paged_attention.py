"""Paged-attention kernels (ref capability: PaddleNLP ``llm`` block KV
cache, ``fused_multi_transformer_op.cu``).

The KV cache is a POOL of fixed-size blocks ([num_blocks, block_size,
H_kv, D]) shared by all sequences; each sequence owns a row of
``block_tables`` (pool indices, scalar-prefetched). Both kernels read a
sequence's blocks where the pool stores them: the gathered K/V is NEVER
materialised, HBM holds pool ≈ Σ actual lengths (not B × max_len), and
neither asks for a transposed or reshaped copy of a pool.

* **decode** — q [B, H, D] (one token per sequence), grid (B,): one step
  per sequence. The pools stay in HBM as stored; the kernel walks the
  row's LIVE blocks only (lens [B], the window, cp ownership), copying
  ``decode_blocks_per_step`` ``[bs, H_kv, D]`` slabs at a time (every K/V
  head of a block in one copy, so GQA fetches nothing twice) into two
  VMEM slots a pool, the next compute block in flight under this one's
  arithmetic, and scores a compute block in one masked matmul.
* **chunk** (ISSUE 11) — the ragged MULTI-query forward behind chunked
  prefill and the spec-decode ``(slots, k+1)`` verify batch: q
  [A, C, H, D] chunk queries at positions ``offsets[a] ..
  offsets[a]+chunk_lens[a]-1``, attending causally over the slot's whole
  pool prefix. Grid (A, q tiles): one step a row and ``chunk_q_tile``
  folded query rows (the H/H_kv query heads of a KV head fold into the
  rows, so GQA repeats no K/V and scores no other head's keys). A dead
  row (``chunk_lens`` 0) copies and computes nothing and emits zeros; a
  live one walks the same slabs by the same copies, from the window's
  first block to the tile's causal frontier, a loop over the K/V heads
  inside, the scores transposed (keys down the sublanes). The wrapper's
  ``pallas_call`` sits under a ``jit`` of its own: the layers of a
  program share one traced and lowered call.

Mosaic copies a slab only as whole tiles of the pool's layout
(``decode_slab_is_tiled``: D in 128 lanes, H_kv in whole sublane tiles,
which every 128-wide GQA/MHA family meets); head_dim 64 and bf16/int8
MQA pools take the XLA gather, in both dispatchers. Unused table slots
hold the OOB sentinel (= num_blocks): neither kernel reads them.

Dispatch functions (``paged_decode_attention`` /
``paged_chunk_attention``) pick Pallas on TPU and the XLA gather
reference elsewhere, from the backend and the shapes alone
(``mosaic_kernels_apply``, ``decode_slab_is_tiled``); no environment
variable or option chooses. On TPU a kernel that fails to trace or
lower raises: there is no downgrade to the gather path. Off the TPU a
test runs a kernel through ``*_pallas(..., interpret=True)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import mosaic_kernels_apply

_NEG_INF = -1e30

# trace-time breadcrumbs ("chunk:xla", "chunk:pallas", ...): one entry
# per DISPATCH TRACE, so tests can assert which implementation a jitted
# program actually baked in (a program served from a jit cache appends
# nothing: it was traced once)
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
# verify batch is the same program at C = k+1. The kernel is the decode
# kernel's scheme with a tile of queries in place of one token: rows (and
# q tiles) are the grid, the pools stay in HBM as stored, and a loop walks
# the row's live compute blocks only, up to the tile's causal frontier.

# VMEM the float32 accumulator of one grid step may fill: it holds a
# ``[D]`` row for every folded query row (position x grouped head) of
# every K/V head, so the q tile follows from it and from the shape.
_CHUNK_ACC_BYTES = 4 << 20
_CHUNK_Q_TILE_MAX = 1024
# scoped VMEM the kernel may ask for (a v5e core has 128 MiB): at the
# Mistral cell's shapes the q and o tiles (two buffers each), the
# accumulator, four slab buffers, the compute block in float32, its mask
# and the scores in flight come to some 22 MB
_CHUNK_VMEM_LIMIT = 48 << 20


def chunk_q_tile(cg, h_kv, d):
    """Folded query rows (positions x grouped heads) one grid step of the
    chunk kernel scores: what ``_CHUNK_ACC_BYTES`` holds for ``h_kv``
    heads, in whole 128-lane rows (the queries lie along the lanes of the
    scores), no more than the folded chunk ``cg`` needs."""
    fit = _CHUNK_ACC_BYTES // (h_kv * d * 4) // 128 * 128
    return min(max(fit, 128), _CHUNK_Q_TILE_MAX, -(-cg // 128) * 128)


def _paged_chunk_kernel(tables_ref, offs_ref, cls_ref, q_ref, k_hbm, v_hbm,
                        *rest, block_size, scale, max_blocks, per_step,
                        group, window, quantized, partials, n_pool):
    """Grid (A, q tiles): one step scores ``q_tile`` folded query rows
    (folded row r = query position r // group, grouped head r % group) of
    every K/V head of one sequence; ``q_ref`` is ``[1, H_kv, q_tile, D]``.
    A tile past ``chunk_lens`` (a dead row: every tile) copies nothing,
    computes nothing and emits zeros.

    The pools stay in HBM as stored (``[N, bs, H_kv, D]``). A loop over
    the compute blocks from the window's first block to the tile's causal
    frontier (the block of its last live query, nothing past it) gathers
    ``per_step`` table entries each, one ``[bs, H_kv, D]`` slab per entry
    and pool, into one of two VMEM slots, the next compute block's copies
    in flight under this one's arithmetic. An inner loop over the K/V
    heads scores the head's folded queries against the head's own
    ``[T, D]`` keys (GQA spends no FLOP on another head's columns): the
    compute block is widened to float32 once, because a sublane of a
    packed (bf16, int8) tile cannot be addressed by a traced head index
    and one of a 32-bit tile can.

    The scores are kept TRANSPOSED, ``[T, q_tile]``: keys down the
    sublanes, queries along the lanes. The softmax's reductions over the
    keys are then elementwise over vregs (a reduction along the lanes is
    half the time of the kernel written the other way), and its state is
    dense: m and l one ``[1, q_tile]`` row a head, the accumulator
    ``[D, q_tile]`` (``V^T P^T``), transposed back once when the tile is
    emitted. Online softmax in float32; the matmuls take their operands in
    the queries' dtype.

    ``quantized`` (static): int8 pools; the scales arrive gathered along
    the table, a ``[1, T]`` row a head and compute block, turned into a
    column here, and multiply scores (K) and probabilities (V) by key.
    ``partials`` (static, context parallelism): table entries >=
    ``n_pool`` belong to another shard and are neither fetched nor
    scored; emit the raw (acc, m, l)."""
    if quantized:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    if partials:
        o_ref, m_ref, l_ref = rest[:3]
        rest = rest[3:]
    else:
        o_ref = rest[0]
        rest = rest[1:]
    kbuf, vbuf, sems, kf, vf, bias, acc, m_scr, l_scr = rest
    i = pl.program_id(0)
    t = pl.program_id(1)
    bs, P = block_size, per_step
    h_kv, qt, d = q_ref.shape[1:]
    T = P * bs                        # keys of one compute block

    off = offs_ref[i]
    live_rows = cls_ref[i] * group    # folded rows that carry a query
    r0 = t * qt                       # the tile's first folded row
    tile_live = r0 < live_rows

    @pl.when(jnp.logical_not(tile_live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        if partials:
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(tile_live)
    def _():
        # the causal frontier: the block of the tile's last live query
        q_last = off + (jnp.minimum(r0 + qt, live_rows) - 1) // group
        n_live = q_last // bs + 1
        first = 0
        if window is not None:
            # blocks entirely below the first query's window are invisible
            # to every query of the tile
            first = jnp.maximum(off + r0 // group - window + 1, 0) // bs
        c_lo = first // P
        c_hi = pl.cdiv(n_live, P)

        def entry(j):
            return tables_ref[i, jnp.minimum(j, max_blocks - 1)]

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
                    act(pltpu.make_async_copy(
                        k_hbm.at[blk], kbuf.at[slot, rows], sems.at[0, slot]))
                    act(pltpu.make_async_copy(
                        v_hbm.at[blk], vbuf.at[slot, rows], sems.at[1, slot]))
            jax.lax.fori_loop(0, P, one, None)

        def start(c, slot):
            each_copy(c, slot, lambda copy: copy.start())

        def wait(c, slot):
            each_copy(c, slot, lambda copy: copy.wait())

        # rows no copy fills meet a mask that is ADDED to the scores (K)
        # and probability 0 in the matmul with V: they must be finite, and
        # fresh VMEM need not be
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        start(c_lo, c_lo % 2)        # a live tile has a block: c_lo < c_hi
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, qt), 1)
        qpos = off + (r0 + lane) // group                 # [1, qt]
        key = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)

        def column(row):
            """A scale row [1, T] -> [T, 1], a scale a key."""
            return jnp.broadcast_to(row, (128, T)).T[:, :1]

        def block(c, _):
            slot = c % 2

            @pl.when(c + 1 < c_hi)
            def _():
                start(c + 1, 1 - slot)

            wait(c, slot)
            kf[...] = kbuf[slot].astype(jnp.float32)
            vf[...] = vbuf[slot].astype(jnp.float32)
            # the mask of the compute block, every head's alike: causal
            # (which keeps a live query inside the row's length too), the
            # window, and under ``partials`` the entries this shard owns
            kpos = c * T + key                            # [T, 1]
            if partials:
                owned = jnp.zeros(kpos.shape, jnp.bool_)
                for p in range(P):  # unrolled: a loop cannot carry a mask
                    owned |= ((key // bs == p)
                              & (entry(c * P + p) < n_pool))
                kpos = jnp.where(owned, kpos, jnp.iinfo(jnp.int32).max)
            keep = kpos <= qpos                           # [T, qt]
            if window is not None:
                keep &= kpos > qpos - window
            bias[...] = jnp.where(keep, 0.0, _NEG_INF)

            def head(h, _):
                q = q_ref[0, h]                           # [qt, D]
                k = kf[:, h, :].astype(q.dtype)           # [T, D]
                v = vf[:, h, :].astype(q.dtype)
                s = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quantized:
                    s = s * column(ks_ref[0, h, pl.ds(c, 1), :])
                s = s + bias[...]     # a masked score is _NEG_INF exactly
                m_prev = m_scr[h]                         # [1, qt]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=0, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                prob = jnp.exp(s - m_new)                 # [T, qt]
                if partials:
                    # a query whose visible keys all lie on other shards
                    # has m_new == _NEG_INF and exp(0) == 1 everywhere
                    prob = jnp.where(bias[...] < 0.0, 0.0, prob)
                l_scr[h] = (l_scr[h] * corr
                            + jnp.sum(prob, axis=0, keepdims=True))
                m_scr[h] = m_new
                if quantized:
                    prob = prob * column(vs_ref[0, h, pl.ds(c, 1), :])
                pv = jax.lax.dot_general(                 # V^T P^T
                    v, prob.astype(v.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc[h] = acc[h] * corr + pv               # [D, qt]

            jax.lax.fori_loop(0, h_kv, head, None)

        jax.lax.fori_loop(c_lo, c_hi, block, None)

        # folded rows past chunk_lens are padding: they attended over
        # whatever lay past the row's length, and emit zeros
        real = (r0 + lane) < live_rows                    # [1, qt]
        if partials:
            m_ref[0] = jnp.where(real, m_scr[...], _NEG_INF)
            l_ref[0] = jnp.where(real, l_scr[...], 0.0)

        def emit(h, _):
            out = acc[h]
            if not partials:
                # a row that walked nothing has l == 0: emit 0, not NaN
                out = out / jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, h] = jnp.where(real, out, 0.0).T.astype(o_ref.dtype)

        jax.lax.fori_loop(0, h_kv, emit, None)


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "q_tile", "partials", "interpret"))
def _paged_chunk_call(q, k_pool, v_pool, block_tables, offsets, chunk_lens,
                      k_scale, v_scale, *, scale, window, q_tile, partials,
                      interpret):
    """The ``pallas_call`` of the chunk kernel and the folding around it,
    under one ``jit`` of their own: every layer of a program (and every
    layer body of a looped model) calls the same traced function, so the
    kernel is traced once and lowered to Mosaic once a program, not once a
    call site."""
    a, c, h, d = q.shape
    n, bs, h_kv, _ = k_pool.shape
    group = h // h_kv
    max_blocks = block_tables.shape[1]
    quantized = k_scale is not None
    per_step = decode_blocks_per_step(bs, h_kv, d, k_pool.dtype, max_blocks)
    keys = per_step * bs

    cg = c * group
    if q_tile is None:
        q_tile = chunk_q_tile(cg, h_kv, d)
    n_qt = -(-cg // q_tile)
    # fold the grouped query heads into the row axis: row r of (a, kv) is
    # query position r // group, grouped head r % group — the
    # (head // kv_rep) GQA convention of the decode kernel
    qf = q.reshape(a, c, h_kv, group, d).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(a, h_kv, cg, d)
    if n_qt * q_tile != cg:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, n_qt * q_tile - cg), (0, 0)))

    tables = block_tables.astype(jnp.int32)
    offs = offsets.astype(jnp.int32)
    cls = chunk_lens.astype(jnp.int32)

    def out_tile(i, t, tables, offs, cls):
        return (i, 0, t, 0)

    def stat_tile(i, t, tables, offs, cls):
        return (i, 0, 0, t)

    def q_tile_of(i, t, tables, offs, cls):
        # a dead tile names the first block (the one the steps before it
        # named, past the first live row): the pipeline fetches no queries
        # for it
        live = t * q_tile < cls[i] * group
        return (jnp.where(live, i, 0), 0, jnp.where(live, t, 0), 0)

    def row_of(i, t, tables, offs, cls):
        return (jnp.where(cls[i] > 0, i, 0), 0, 0, 0)

    in_specs = [pl.BlockSpec((1, h_kv, q_tile, d), q_tile_of),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [qf, k_pool, v_pool]
    if quantized:
        # the scales are small (4 bytes a position and head): gathered
        # along the table here, a row of compute blocks a sequence and
        # head (an entry past the row's blocks reads as scale 0, not as
        # whatever the block the sentinel clamps onto holds)
        n_steps = -(-max_blocks // per_step)
        clamped = jnp.minimum(tables, n - 1)

        def along_table(pool):
            g = jnp.where((tables < n)[:, :, None, None],
                          jnp.take(pool, clamped, axis=0), 0.0)
            g = g.reshape(a, -1, h_kv)
            g = jnp.pad(jnp.moveaxis(g, 2, 1),
                        ((0, 0), (0, 0), (0, n_steps * keys - g.shape[1])))
            return g.reshape(a, h_kv, n_steps, keys)

        in_specs += [pl.BlockSpec((1, h_kv, n_steps, keys), row_of)] * 2
        operands += [along_table(k_scale), along_table(v_scale)]

    rows = n_qt * q_tile
    out_specs = pl.BlockSpec((1, h_kv, q_tile, d), out_tile)
    out_shape = jax.ShapeDtypeStruct((a, h_kv, rows, d), q.dtype)
    if partials:
        # acc in f32 (the merge renormalises before the dtype cast) plus
        # the m and l rows
        out_specs = [out_specs] + [
            pl.BlockSpec((1, h_kv, 1, q_tile), stat_tile)] * 2
        out_shape = [jax.ShapeDtypeStruct((a, h_kv, rows, d), jnp.float32),
                     jax.ShapeDtypeStruct((a, h_kv, 1, rows), jnp.float32),
                     jax.ShapeDtypeStruct((a, h_kv, 1, rows), jnp.float32)]
    slots = (2, keys, h_kv, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(a, n_qt),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM(slots, k_pool.dtype),
            pltpu.VMEM(slots, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            # the current compute block in float32, K and V
            pltpu.VMEM(slots[1:], jnp.float32),
            pltpu.VMEM(slots[1:], jnp.float32),
            # its mask as an additive bias, shared by the heads
            pltpu.VMEM((keys, q_tile), jnp.float32),
            # per-head accumulator (transposed), running max, denominator
            pltpu.VMEM((h_kv, d, q_tile), jnp.float32),
            pltpu.VMEM((h_kv, 1, q_tile), jnp.float32),
            pltpu.VMEM((h_kv, 1, q_tile), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_chunk_kernel, block_size=bs,
                               scale=scale, max_blocks=max_blocks,
                               per_step=per_step, group=group,
                               window=window, quantized=quantized,
                               partials=partials, n_pool=n)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # rows and q tiles are independent: every step sets up its own
        # state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
        name="paged_chunk_attention",
    )(tables, offs, cls, *operands)

    def unfold(x):
        x = x[:, :, :cg].reshape(a, h_kv, c, group, *x.shape[3:])
        x = jnp.moveaxis(x, 1, 2)                  # [A, C, H_kv, group, ..]
        return x.reshape(a, c, h, *x.shape[4:])

    if partials:
        acc, m, l = out
        return unfold(acc), unfold(m[:, :, 0]), unfold(l[:, :, 0])
    return unfold(out)


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
    dead, and so are a live row's positions past chunk_lens (output 0).
    Returns [A, C, H, D] — or, with ``partials=True`` (context
    parallelism), the raw (acc [A, C, H, D] f32, m [A, C, H] f32,
    l [A, C, H] f32) triple over owned table entries only.

    The pools are handed to the kernel in HBM as they are stored: no
    transpose, no pool-sized temporary. Compiled (``interpret=False``)
    the shape has to meet ``decode_slab_is_tiled``, and a ``q_tile``
    given by hand (``chunk_q_tile`` otherwise) to be whole 128-lane rows."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_chunk_call(
        q, k_pool, v_pool, jnp.asarray(block_tables), jnp.asarray(offsets),
        jnp.asarray(chunk_lens), k_scale, v_scale,
        scale=float(scale if scale is not None else q.shape[-1] ** -0.5),
        window=window, q_tile=q_tile, partials=partials,
        interpret=bool(interpret))


def paged_chunk_attention_xla(q, k_pool, v_pool, block_tables, offsets,
                              chunk_lens, *, scale=None, window=None,
                              k_scale=None, v_scale=None, partials=False):
    """Gather-based reference path (CPU / fallback): materialise each
    row's whole ``max_blocks*bs`` pool view and run dense masked
    attention — exactly the pre-kernel ``llama_prefill_chunk_paged``
    inner loop: the path off the TPU and for pools whose slabs Mosaic
    cannot copy. ``partials=True`` returns the (acc, m, l) triple over
    owned table entries only (context parallelism)."""
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
    """Dispatch for the ragged chunk path, as the decode dispatch: the
    Pallas kernel on TPU for pools whose slabs Mosaic can copy
    (``decode_slab_is_tiled``), the XLA gather elsewhere.
    ``k_scale``/``v_scale`` [N, bs, H_kv] f32 mark an int8 pool —
    dequantize-on-read in both paths. On TPU a Pallas failure raises."""
    if k_scale is not None:
        _note_trace("chunk:int8-kv")
    if partials:
        _note_trace("chunk:partials")
    if mosaic_kernels_apply():
        if decode_slab_is_tiled(*k_pool.shape[2:], k_pool.dtype):
            out = paged_chunk_attention_pallas(
                q, k_pool, v_pool, block_tables, offsets, chunk_lens,
                scale=scale, window=window, k_scale=k_scale,
                v_scale=v_scale, partials=partials, interpret=interpret)
            _note_trace("chunk:pallas")
            return out
        _note_trace("chunk:slab-off-tiling")
    _note_trace("chunk:xla")
    return paged_chunk_attention_xla(
        q, k_pool, v_pool, block_tables, offsets, chunk_lens,
        scale=scale, window=window, k_scale=k_scale, v_scale=v_scale,
        partials=partials)
