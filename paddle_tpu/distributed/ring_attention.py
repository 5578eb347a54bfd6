"""Ring attention — sequence/context parallelism for long sequences.

Reference capability: PaddleNLP sequence-parallel + the reference's
``paddle.distributed.fleet`` sep-parallel group (``sep_degree``); the TPU
design follows the ring-attention formulation (blockwise attention with KV
rotation over the ``sp`` axis) so attention over a sequence sharded across
chips never materialises the full S×S score matrix and overlaps KV transfer
with compute (ppermute rides ICI while the MXU works on the current block).

Use inside ``shard_map`` with the sequence axis sharded on ``sp``:
each member holds q,k,v of shape [B, S/sp, H, D].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

_NEG_INF = -1e30


def _block_attend(q, k, v, scale, mask, bias=None):
    """Scores for one (q_block, kv_block) pair in fp32.
    q: [B,Sq,H,D] k,v: [B,Sk,Hkv,D]; mask: bool, broadcastable to
    [B,H,Sq,Sk] (e.g. [1,1,Sq,Sk] causal or [B,1,Sq,Sk] varlen), or None.
    ``bias``: ADDITIVE float scores (T5 relative bias / ALiBi),
    broadcastable to [B,H,Sq,Sk]; applied after scaling, before the mask.
    GQA (Hkv < H) runs as a grouped einsum — repeated K/V is never
    materialised, so the ring rotates 1/rep the bytes."""
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hq != hk:
        rep = hq // hk
        qg = q.reshape(b, sq, hk, rep, d)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(b, hq, sq, sk)  # head h = g*rep + r (q head order)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    if mask is not None:
        # a fully-masked row has m = NEG_INF and exp(s - m) = 1 — zero the
        # masked entries explicitly so dead rows contribute l = 0, not Sk
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Sq]
    if hq != hk:
        pg = p.reshape(b, hk, rep, sq, sk).astype(v.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", pg, v).reshape(b, sq, hq, d)
        o = o.astype(jnp.float32)
    else:
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v
                       ).astype(jnp.float32)
    return o, m, l


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                   scale: float | None = None, window: int | None = None,
                   kv_lens=None, attn_mask=None, attn_bias=None):
    """Blockwise ring attention with online-softmax accumulation.

    Equals full attention over the gathered sequence (see
    tests/test_ring_attention.py). Gradient flows through ppermute, so the
    backward pass is itself a ring pass — no full-sequence gather ever.
    ``window``: Mistral-style causal sliding window over GLOBAL positions
    (query position i sees [i-window+1, i] across shard boundaries).
    ``kv_lens``: [B] GLOBAL valid key lengths (padded-varlen batches) —
    per-step masking against the rotating block's global key positions, no
    mask tensor materialised.
    ``attn_mask``: [B, S_loc, S_global] bool — this rank's query rows vs
    ALL global key columns (the O(S^2/sp)-per-device general-mask path);
    each ring step slices the arriving block's column range.
    ``attn_bias``: [B|1, H|1, S_loc, S_global] float ADDITIVE scores (T5
    relative bias, ALiBi) — same row/column layout as ``attn_mask``, with
    a broadcastable head dim; sliced per ring step like the mask. Must be
    finite (use ``attn_mask`` to fully block positions). Differentiable —
    d(bias) flows back through the per-step slices.
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5

    o = jnp.zeros((b, s_loc, h, d), jnp.float32)
    m = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_loc), jnp.float32)

    causal_in_block = jnp.tril(jnp.ones((s_loc, s_loc), bool)) if causal else None
    a_ix = jnp.arange(s_loc)[:, None]
    b_ix = jnp.arange(s_loc)[None, :]
    perm = [(i, (i + 1) % n) for i in range(n)]

    # a window bounds how far back any query looks: ring step s covers
    # global distance >= s*s_loc - (s_loc-1) on every rank, so steps past
    # ceil((window + s_loc - 1) / s_loc) are dead EVERYWHERE — prune them
    # at trace time (no compute, no ppermute): windowed ring costs
    # O(S * window), not O(S^2)
    live_steps = n
    if causal and window is not None:
        live_steps = min(n, -(-(window + s_loc - 1) // s_loc))

    k_blk, v_blk = k, v
    for step in range(live_steps):
        src = (my - step) % n  # which sequence block k_blk/v_blk holds
        if causal:
            # src > my: future block — fully masked; src == my: in-block causal
            block_mask = jnp.where(src == my, causal_in_block,
                                   jnp.full((s_loc, s_loc), True))
            allowed = (src <= my)
            if window is not None:
                # global-position band: qg - kg < window
                dist = (my - src) * s_loc + a_ix - b_ix
                block_mask = block_mask & (dist < window)
                allowed = allowed & ((my - src) * s_loc - (s_loc - 1) < window)
            block_mask = block_mask[None, None]  # [1,1,Sq,Sk]
        else:
            block_mask = None
            allowed = True
        if kv_lens is not None:
            # this block's keys hold global positions src*s_loc + [0, s_loc)
            g_idx = src * s_loc + jnp.arange(s_loc)
            key_ok = (g_idx[None, :] < jnp.asarray(kv_lens)[:, None]
                      )[:, None, None, :]  # [B,1,1,Sk]
            block_mask = key_ok if block_mask is None else block_mask & key_ok
        if attn_mask is not None:
            cols = lax.dynamic_slice_in_dim(attn_mask, src * s_loc, s_loc,
                                            axis=2)  # [B, Sq, Sk]
            cols = cols[:, None]  # [B,1,Sq,Sk]
            block_mask = cols if block_mask is None else block_mask & cols
        bias_blk = None
        if attn_bias is not None:
            bias_blk = lax.dynamic_slice_in_dim(attn_bias, src * s_loc,
                                                s_loc, axis=3)
        o_b, m_b, l_b = _block_attend(q, k_blk, v_blk, scale, block_mask,
                                      bias_blk)
        if causal:
            o_b = jnp.where(allowed, o_b, 0.0)
            m_b = jnp.where(allowed, m_b, _NEG_INF)
            l_b = jnp.where(allowed, l_b, 0.0)
        # online softmax merge
        m_new = jnp.maximum(m, m_b)
        c1 = jnp.exp(m - m_new)
        c2 = jnp.exp(m_b - m_new)
        o = o * jnp.moveaxis(c1, 1, 2)[..., None] + o_b * jnp.moveaxis(c2, 1, 2)[..., None]
        l = l * c1 + l_b * c2
        m = m_new
        if step != live_steps - 1:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    out = o / jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return out.astype(q.dtype)


# -- online-softmax partial merges (context-parallel serving, ISSUE 18) -----
#
# The paged attention kernels emit per-shard (acc, m, l) partials in the
# TRAILING-head layout — o [..., H, D] with m, l shaped o.shape[:-1] — and
# the serving engine combines them across the ``cp`` mesh axis. Both merge
# strategies below are DETERMINISTIC ACROSS MEMBERS: every shard folds the
# same partials in the same global order (ring) or through symmetric
# reductions (psum), so the merged result is bit-identical on every member
# and replicated sampling / quantize-on-write scatters never diverge.

def merge_partials(o, m, l, o_b, m_b, l_b):
    """One pairwise online-softmax merge of two partial triples
    (trailing-head layout: m/l shaped ``o.shape[:-1]``)."""
    m_new = jnp.maximum(m, m_b)
    c1 = jnp.exp(m - m_new)
    c2 = jnp.exp(m_b - m_new)
    return (o * c1[..., None] + o_b * c2[..., None], m_new,
            l * c1 + l_b * c2)


def finalize_partials(o, l, dtype=None):
    """Normalise a merged accumulator; ``max(l, eps)`` keeps fully-masked
    rows (padding / all keys on other shards pre-merge) at 0, not NaN."""
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out if dtype is None else out.astype(dtype)


def ring_merge_partials(o, m, l, axis_name: str = "cp"):
    """Ring merge: rotate the triples with ppermute (the same rotation
    pattern the training ring uses for KV blocks) until every member has
    collected all ``n`` shard partials, then fold them in GLOBAL shard
    order 0..n-1. The fold's fp rounding sequence is identical on every
    member — unlike folding in arrival order, which would differ per
    member by a rotation and break the bit-identical-replicas contract."""
    n = axis_size(axis_name)
    if n == 1:
        return o, m, l
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    os_, ms_, ls_ = [o], [m], [l]
    ob, mb, lb = o, m, l
    for _ in range(n - 1):
        ob = lax.ppermute(ob, axis_name, perm)
        mb = lax.ppermute(mb, axis_name, perm)
        lb = lax.ppermute(lb, axis_name, perm)
        os_.append(ob)
        ms_.append(mb)
        ls_.append(lb)
    # after s rotations the copy at stack position s came from member
    # (my - s) % n — shard g therefore sits at position (my - g) % n
    take = (my - jnp.arange(n)) % n

    def reorder(xs):
        return jnp.take(jnp.stack(xs), take, axis=0)

    o_s, m_s, l_s = reorder(os_), reorder(ms_), reorder(ls_)
    o_a, m_a, l_a = o_s[0], m_s[0], l_s[0]
    for g in range(1, n):
        o_a, m_a, l_a = merge_partials(o_a, m_a, l_a,
                                       o_s[g], m_s[g], l_s[g])
    return o_a, m_a, l_a


def psum_merge_partials(o, m, l, axis_name: str = "cp"):
    """Flat merge through symmetric reductions: one pmax for the global
    row max, one fused psum for the rescaled (acc, l). O(heads·dim)
    bytes per member per step — the decode-tick cross-shard merge.
    pmax/psum are member-order-invariant, so the result is bit-identical
    on every member by construction."""
    if axis_size(axis_name) == 1:
        return o, m, l
    m_max = lax.pmax(m, axis_name)
    c = jnp.exp(m - m_max)
    o, l = lax.psum((o * c[..., None], l * c), axis_name)
    return o, m_max, l


def bias_spec(bias_shape, head_spec, batch_axes=("dp", "fsdp"),
              rows_axis="sp"):
    """PartitionSpec for a [B|1, H|1, Sq, Sk] additive bias: shard only the
    non-broadcast dims (a size-1 batch/head dim must stay replicated)."""
    from jax.sharding import PartitionSpec as P
    b_ax = batch_axes if bias_shape[0] > 1 else None
    h_ax = head_spec if bias_shape[1] > 1 else None
    return P(b_ax, h_ax, rows_axis, None)


def make_ring_attention(mesh, causal=True, head_spec=None, window=None,
                        varlen=False, masked=False, bias_shape=None,
                        scale=None):
    """shard_map-wrapped ring attention: global [B, S, H, D] with S sharded
    over sp; drop-in replacement for full attention. ``head_spec="tp"``
    composes with tensor parallelism (heads stay tp-sharded through the
    ring — each tp member rings its own head slice over sp); ``window``
    applies a global causal sliding window (Mistral).
    ``varlen=True``: attend(q, k, v, kv_lens) with [B] global key lengths.
    ``masked=True``: attend(..., attn_mask) with a [B, S, S] bool mask
    (sharded on q rows); combine with varlen by passing both in order.
    ``bias_shape``: pass the [B|1, H|1, S, S] shape of an ADDITIVE float
    bias (T5 relative bias, ALiBi) to accept it as the last argument —
    q rows sharded over sp, head dim over ``head_spec`` when per-head."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(("dp", "fsdp"), "sp", head_spec, None)
    in_specs = [spec, spec, spec]
    if varlen:
        in_specs.append(P(("dp", "fsdp")))            # kv_lens [B]
    if masked:
        # [B, S, S_global]: q rows sharded over sp, key columns replicated
        in_specs.append(P(("dp", "fsdp"), "sp", None))
    if bias_shape is not None:
        in_specs.append(bias_spec(bias_shape, head_spec))

    @functools.partial(shard_map, mesh=mesh.mesh,
                       in_specs=tuple(in_specs), out_specs=spec)
    def attend(q, k, v, *extra):
        it = iter(extra)
        lens = next(it) if varlen else None
        mask = next(it) if masked else None
        bias = next(it) if bias_shape is not None else None
        return ring_attention(q, k, v, axis_name="sp", causal=causal,
                              scale=scale, window=window, kv_lens=lens,
                              attn_mask=mask, attn_bias=bias)

    return attend


# -- zigzag (load-balanced causal) ring attention ----------------------------
#
# With contiguous block sharding, causal masking makes rank r do r+1 visible
# kv blocks while rank 0 does one — the ring's wall-clock is set by the last
# rank (~2× waste). Zigzag assignment (rank r holds chunks r and 2n-1-r of a
# 2n-chunk split) gives every rank one early and one late chunk, so visible
# work is equal across ranks. Same trick as the public zigzag/striped ring
# attention formulations; outputs stay in zigzag layout (invert with
# zigzag_inverse_permutation).

def zigzag_permutation(seq_len: int, n_shards: int):
    """Index array mapping zigzag order → original positions: apply
    ``x[:, perm]`` BEFORE sharding on sp."""
    import numpy as np
    assert seq_len % (2 * n_shards) == 0, "2*n_shards must divide seq_len"
    c = seq_len // (2 * n_shards)
    order = []
    for r in range(n_shards):
        order.extend(range(r * c, (r + 1) * c))                       # chunk r
        order.extend(range((2 * n_shards - 1 - r) * c,
                           (2 * n_shards - r) * c))                   # chunk 2n-1-r
    return np.asarray(order)


def zigzag_inverse_permutation(seq_len: int, n_shards: int):
    import numpy as np
    perm = zigzag_permutation(seq_len, n_shards)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len)
    return inv


def zigzag_ring_attention(q, k, v, *, axis_name: str = "sp",
                          scale: float | None = None):
    """Causal ring attention over zigzag-laid-out shards (see
    zigzag_permutation). [B, S/sp, H, D] per member; the local sequence is
    [chunk_my, chunk_{2n-1-my}] (chunks A and B).

    Per ring step this computes exactly TWO s2×s2 block-attends — the dead
    quadrants are never evaluated, which is the point of the zigzag layout.
    With chunk ids a = my < n ≤ b = 2n-1-my and kv ids c = src, d = 2n-1-src:
      * A never sees D (a < n ≤ d), B always fully sees C (b ≥ n > c)
      * step 0 (src == my): A·C causal + B·C full + B·D causal
      * src < my: A·C full + B·C full          (B·D dead: b < d)
      * src > my: B·C full + B·D full          (A·C dead: a < c)
    The traced src<my / src>my choice is made by SELECTING OPERANDS
    (qA vs qB, C vs D) into one dense block-attend — shapes stay static.
    """
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    assert s_loc % 2 == 0, "zigzag needs an even local length"
    s2 = s_loc // 2
    scale = scale if scale is not None else d ** -0.5

    tril = jnp.tril(jnp.ones((s2, s2), bool))
    qA, qB = q[:, :s2], q[:, s2:]

    # accumulators per half
    def zero_acc():
        return (jnp.zeros((b, s2, h, d), jnp.float32),
                jnp.full((b, h, s2), _NEG_INF, jnp.float32),
                jnp.zeros((b, h, s2), jnp.float32))

    accA, accB = zero_acc(), zero_acc()

    def merge(acc, o_b, m_b, l_b):
        o, m, l = acc
        m_new = jnp.maximum(m, m_b)
        c1 = jnp.exp(m - m_new)
        c2 = jnp.exp(m_b - m_new)
        o = (o * jnp.moveaxis(c1, 1, 2)[..., None]
             + o_b * jnp.moveaxis(c2, 1, 2)[..., None])
        return o, m_new, l * c1 + l_b * c2

    def merge_where(pred, acc, o_b, m_b, l_b):
        """Merge only where pred (per-member traced bool)."""
        o, m, l = acc
        o2, m2, l2 = merge(acc, o_b, m_b, l_b)
        sel = lambda x2, x1: jnp.where(pred, x2, x1)
        return sel(o2, o), sel(m2, m), sel(l2, l)

    perm = [(i, (i + 1) % n) for i in range(n)]
    k_blk, v_blk = k, v
    for step in range(n):
        kC, vC = k_blk[:, :s2], v_blk[:, :s2]
        kD, vD = k_blk[:, s2:], v_blk[:, s2:]
        if step == 0:
            # diagonal: A·A causal, B·[A full | B causal]
            accA = merge(accA, *_block_attend(qA, kC, vC, scale, tril))
            accB = merge(accB, *_block_attend(qB, kC, vC, scale, None))
            accB = merge(accB, *_block_attend(qB, kD, vD, scale, tril))
        else:
            src = (my - step) % n
            pred = src < my              # else src > my (never equal here)
            # block 1: B·C — visible in both cases
            accB = merge(accB, *_block_attend(qB, kC, vC, scale, None))
            # block 2: A·C (pred) or B·D (!pred) — select operands, one dense
            qx = jnp.where(pred, qA, qB)
            ky = jnp.where(pred, kC, kD)
            vy = jnp.where(pred, vC, vD)
            o_b, m_b, l_b = _block_attend(qx, ky, vy, scale, None)
            accA = merge_where(pred, accA, o_b, m_b, l_b)
            accB = merge_where(~pred, accB, o_b, m_b, l_b)
        if step != n - 1:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    def finalize(acc):
        o, m, l = acc
        return o / jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)

    out = jnp.concatenate([finalize(accA), finalize(accB)], axis=1)
    return out.astype(q.dtype)


def make_zigzag_ring_attention(mesh):
    """shard_map-wrapped zigzag ring attention (inputs already in zigzag
    layout, S sharded over sp)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(("dp", "fsdp"), "sp", None, None)

    @functools.partial(shard_map, mesh=mesh.mesh,
                       in_specs=(spec, spec, spec), out_specs=spec)
    def attend(q, k, v):
        return zigzag_ring_attention(q, k, v, axis_name="sp")

    return attend
