"""Pallas TPU flash attention (ref capability: ``paddle/phi/kernels/fusion/
flash_attn`` — the CUDA flash-attention kernel family).

TPU-first design (not a CUDA translation):
  * grid (batch*heads, q_blocks, kv_blocks) with the kv dimension iterated
    fastest — the output tile stays resident in VMEM across the kv sweep
    (Pallas keeps revisited blocks live), so the online-softmax accumulator
    never round-trips HBM.
  * fp32 accumulation in VMEM scratch; bf16 inputs feed the MXU directly.
  * backward = two kernels (dq-major and dkv-major sweeps) from saved
    (O, logsumexp), the standard flash-2 recomputation strategy.
  * causal blocks that are fully masked are skipped with @pl.when — the
    sweep does ~half the FLOPs for causal attention.
  * sliding-window (Mistral) runs on a BANDED grid: each q block's k-axis
    only spans its band (index_map offsets the block index), so both the
    FLOPs and the K/V DMA traffic are O(S*window), not O(S^2).

Layout: [B, S, H, D] at the API (reference flash_attention convention);
kernels run on [B*H, S, D].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Swept on v5e (round 4's sweep, script deleted in PR 30: B4 S2048 H16 D128 causal):
# 128/128 ran 9.9ms fwd / 29.6ms fwd+bwd; 512/1024 runs 4.5 / 14.0 —
# a single 128^3 MXU issue per grid step can't hide the loop overhead.
# (1024/1024 measured equal within noise; 512 keeps the q tile usable
# at shorter sequence lengths.)
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30
_float0 = jax.dtypes.float0

# Declaring the (batch-head, major, minor) grid as (parallel, parallel,
# arbitrary) lets Mosaic pipeline DMAs across grid steps instead of
# serialising them. Measured on v5e (round 4's sweep, script deleted in PR 30: S=4096
# w=1024, dispatch floor subtracted): full causal 3.25ms -> 0.92ms, banded
# 2.12ms -> 0.77ms — and only WITH this declared does the banded O(S*W)
# grid actually beat full causal on-chip (r3 finding: 6.5x slower without).
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY))


def _band_mask(s, i, j, block_q, block_k, causal, window, q_off, klen=None,
               sk=None):
    """Apply causal/sliding-window banding and (padded-varlen) key-length
    masking to a score tile. ``q_off`` (= sk - sq) aligns query positions to
    the END of the key axis so a short query block (KV-cache decode) sees
    the whole prefix. ``klen`` (traced scalar) masks keys >= the row's valid
    length — the reference's padded/varlen flash_attn capability. With
    klen AND q_off > 0 (decode against a PADDED cache, flash-attn's
    cache_seqlens form) query positions end-align to the row's valid
    length: position of query i is ``klen - sq + i``, so the whole
    computation equals a solo call against the trimmed cache."""
    off = _q_offset(q_off, klen, sk) if sk is not None else q_off
    q_idx = off + i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = q_idx >= k_idx if causal else (q_idx == q_idx)
    if window is not None:
        keep &= (q_idx - k_idx) < window
    if klen is not None:
        keep &= k_idx < klen
    return jnp.where(keep, s, _NEG_INF)


def _block_live(i, j, block_q, block_k, causal, window, q_off, klen=None):
    """Predicate: tile (i, j) has any unmasked entry — causal upper bound,
    with a window a lower band bound (skip tiles fully below it), and with
    varlen a key-length bound (skip tiles entirely in the padding)."""
    live = jnp.asarray(True)
    if causal:
        live &= j * block_k <= q_off + i * block_q + block_q - 1
    if window is not None:
        live &= q_off + i * block_q - (j * block_k + block_k - 1) < window
    if klen is not None:
        live &= j * block_k < klen
    return live


def _q_offset(q_off, klen, sk):
    """Query-position offset shared by the masks and ALiBi: buffer-end
    alignment (``sk - sq``) normally; with ``kv_lens`` AND a short query
    block (``q_off > 0``, decode against a PADDED cache) positions
    end-align to the row's VALID length (``klen - sq``) — ONE rule, so the
    bias and the masks can never disagree."""
    if klen is None or q_off == 0:
        return q_off
    return q_off + klen - sk


def _alibi_add(s, slope, i, j, block_q, block_k, a_off, causal):
    """Fused ALiBi, computed from iota IN-KERNEL — the O(S^2) bias tensor
    the XLA path materialises never exists here (the flash-attn CUDA
    kernel's alibi_slopes capability, TPU-style). Causal: the standard
    ``-slope * (q_pos - k_pos)`` decay; non-causal: symmetric
    ``-slope * |q_pos - k_pos|`` (flash-attn's bidirectional form).
    ``a_off`` aligns query positions: ``sk - sq`` for decode against an
    un-padded cache, ``klen - sq`` (traced) when ``kv_lens`` marks the
    valid cache length."""
    q_idx = a_off + i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           s.shape, 0)
    k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    d = (k_idx - q_idx).astype(jnp.float32)
    return s + slope * (d if causal else -jnp.abs(d))


def _kv_row_index(kv_rep):
    """Index map factory for K/V block specs: q row b reads kv row
    b // kv_rep (identity when there is no GQA — keeps the non-GQA path
    free of the division)."""
    if kv_rep == 1:
        return lambda b, second, third: (b, third, 0)
    return lambda b, second, third: (b // kv_rep, third, 0)


def _band_j_start(i, block_q, block_k, window, q_off):
    """First k-block index in the band of q-block i (clamped to 0)."""
    return jnp.maximum(0, (i * block_q + q_off - window + 1) // block_k)


def _band_i_start(j, block_q, block_k, q_off):
    """First q-block index whose band reaches k-block j (clamped to 0)."""
    return jnp.maximum(0, (j * block_k - q_off) // block_q)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                scale, causal, window, q_off, sk, block_q, block_k, nk,
                banded, nsteps, has_lens, has_slopes):
    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    slopes_ref = rest.pop(0) if has_slopes else None
    o_ref, lse_ref, acc, m_sc, l_sc = rest
    b = pl.program_id(0)
    # lens/slopes ride whole-array in SMEM (a [BH, 1] VMEM block would
    # violate the (8, 128) tile rule); index by the batch-head grid row
    klen = lens_ref[b, 0] if has_lens else None
    i, jl = pl.program_id(1), pl.program_id(2)
    # banded grid: the j-axis is a window-relative offset from the first
    # live k block of this q block; full grid: jl IS the k block index
    j = _band_j_start(i, block_q, block_k, window, q_off) + jl if banded else jl

    @pl.when(jl == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def compute():
        q = q_ref[0]  # [Bq, D]
        k = k_ref[0]  # [Bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_slopes:
            s = _alibi_add(s, slopes_ref[b, 0], i, j, block_q, block_k,
                           _q_offset(q_off, klen, sk), causal)
        if causal or window is not None or has_lens:
            s = _band_mask(s, i, j, block_q, block_k, causal, window, q_off,
                           klen, sk)
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

    if banded:
        pl.when(_block_live(i, j, block_q, block_k, causal, window, q_off,
                            klen) & (j < nk))(compute)
    elif causal or has_lens:
        # block (i, j) has any unmasked entry iff j*Bk <= i*Bq + Bq - 1
        # (windowed: not entirely below the band; varlen: not all padding)
        pl.when(_block_live(i, j, block_q, block_k, causal, window, q_off,
                            klen))(compute)
    else:
        compute()

    @pl.when(jl == nsteps - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:], 1e-30)  # [Bq, 1]
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        # lse is [Bq, 1]: kept 2D with q on the sublane dim so the block
        # tiling is TPU-legal and it broadcasts against [Bq, Bk] scores.
        # Fully-masked rows (q in the padding of a varlen batch): l == 0 —
        # emit lse = 0 so the backward's exp(s - lse) underflows to 0
        # instead of exploding (s = -1e30, a real lse would be ~-1e30 too).
        lse = m_sc[:] + jnp.log(l)
        if has_lens:
            lse = jnp.where(l_sc[:] > 0, lse, 0.0)
        lse_ref[0] = lse.astype(lse_ref.dtype)


def _flash_fwd(q, k, v, lens, slopes, *, scale, causal, window, kv_rep,
               block_q, block_k, interpret):
    bh, s, d = q.shape
    sk = k.shape[1]
    q_off = sk - s  # align queries to the end of the key axis (decode)
    has_lens = lens is not None
    has_slopes = slopes is not None
    # GQA: k/v carry bh/kv_rep batch-head rows; q row b reads kv row
    # b // kv_rep via the index map — no repeated K/V is ever materialised
    nq, nk = pl.cdiv(s, block_q), pl.cdiv(sk, block_k)
    # windowed-causal: visit only the k blocks inside each q block's band —
    # the DMA pipeline then moves O(S*window) bytes, not O(S^2)
    banded = window is not None and causal and window < sk
    if banded:
        nsteps = min(nk, pl.cdiv(window + block_q - 1, block_k) + 1)

        def kv_index(b, i, jl):
            j = _band_j_start(i, block_q, block_k, window, q_off) + jl
            return (b // kv_rep, jnp.minimum(j, nk - 1), 0)
    else:
        nsteps = nk
        kv_index = _kv_row_index(kv_rep)
    grid = (bh, nq, nsteps)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, q_off=q_off, sk=sk,
                               block_q=block_q,
                               block_k=block_k, nk=nk, banded=banded,
                               nsteps=nsteps, has_lens=has_lens,
                               has_slopes=has_slopes)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    args = [q, k, v]
    if has_lens:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(lens)
    if has_slopes:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(slopes)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return out, lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, window, q_off, sk, block_q, block_k, nk,
               banded, nsteps, has_lens, has_slopes):
    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    slopes_ref = rest.pop(0) if has_slopes else None
    dq_ref, dq_acc = rest
    b = pl.program_id(0)
    klen = lens_ref[b, 0] if has_lens else None
    i, jl = pl.program_id(1), pl.program_id(2)
    j = _band_j_start(i, block_q, block_k, window, q_off) + jl if banded else jl

    @pl.when(jl == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_slopes:
            s = _alibi_add(s, slopes_ref[b, 0], i, j, block_q, block_k,
                           _q_offset(q_off, klen, sk), causal)
        if causal or window is not None or has_lens:
            s = _band_mask(s, i, j, block_q, block_k, causal, window, q_off,
                           klen, sk)
        p = jnp.exp(s - lse_ref[0])  # lse_ref[0]: [Bq, 1]
        dp = jax.lax.dot_general(do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if banded:
        pl.when(_block_live(i, j, block_q, block_k, causal, window, q_off,
                            klen) & (j < nk))(compute)
    elif causal or has_lens:
        pl.when(_block_live(i, j, block_q, block_k, causal, window, q_off,
                            klen))(compute)
    else:
        compute()

    @pl.when(jl == nsteps - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, window, q_off, sk, block_q,
                block_k, nq, banded, nsteps, has_lens, has_slopes):
    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    slopes_ref = rest.pop(0) if has_slopes else None
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    b = pl.program_id(0)
    klen = lens_ref[b, 0] if has_lens else None
    j, il = pl.program_id(1), pl.program_id(2)  # kv-major: q iterated fastest
    i = _band_i_start(j, block_q, block_k, q_off) + il if banded else il

    @pl.when(il == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_slopes:
            s = _alibi_add(s, slopes_ref[b, 0], i, j, block_q, block_k,
                           _q_offset(q_off, klen, sk), causal)
        if causal or window is not None or has_lens:
            s = _band_mask(s, i, j, block_q, block_k, causal, window, q_off,
                           klen, sk)
        p = jnp.exp(s - lse_ref[0])  # [Bq, Bk]; lse_ref[0]: [Bq, 1]
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale  # [Bq, Bk]
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if banded:
        pl.when(_block_live(i, j, block_q, block_k, causal, window, q_off,
                            klen) & (i < nq))(compute)
    elif causal or has_lens:
        # varlen: k blocks fully in the padding keep zero dk/dv (init runs
        # on il==0 regardless, so the outputs are well-defined zeros)
        pl.when(_block_live(i, j, block_q, block_k, causal, window, q_off,
                            klen))(compute)
    else:
        compute()

    @pl.when(il == nsteps - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, *, scale, causal, window, kv_rep, block_q, block_k,
               interpret):
    q, k, v, lens, slopes, out, lse = res
    bh, s, d = q.shape
    sk = k.shape[1]
    bh_kv = k.shape[0]
    q_off = sk - s
    has_lens = lens is not None
    has_slopes = slopes is not None
    nq, nk = pl.cdiv(s, block_q), pl.cdiv(sk, block_k)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, S, 1] to match lse layout

    banded = window is not None and causal and window < sk
    if banded:
        nk_steps = min(nk, pl.cdiv(window + block_q - 1, block_k) + 1)
        nq_steps = min(nq, pl.cdiv(window + block_k - 1, block_q) + 1)

        def kv_index_dq(b, i, jl):
            j = _band_j_start(i, block_q, block_k, window, q_off) + jl
            return (b // kv_rep, jnp.minimum(j, nk - 1), 0)

        def q_index_dkv(b, j, il):
            i = _band_i_start(j, block_q, block_k, q_off) + il
            return (b, jnp.minimum(i, nq - 1), 0)
    else:
        nk_steps, nq_steps = nk, nq

        kv_index_dq = _kv_row_index(kv_rep)

        def q_index_dkv(b, j, il):
            return (b, il, 0)

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_index_dq),
        pl.BlockSpec((1, block_k, d), kv_index_dq),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    dq_args = [q, k, v, g, lse, delta]
    if has_lens:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_args.append(lens)
    if has_slopes:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_args.append(slopes)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, q_off=q_off, sk=sk,
                          block_q=block_q,
                          block_k=block_k, nk=nk, banded=banded,
                          nsteps=nk_steps, has_lens=has_lens,
                          has_slopes=has_slopes),
        grid=(bh, nq, nk_steps),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_attention_dq",
    )(*dq_args)

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), q_index_dkv),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: _kv_row_index(kv_rep)(b, i, j)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: _kv_row_index(kv_rep)(b, i, j)),
        pl.BlockSpec((1, block_q, d), q_index_dkv),
        pl.BlockSpec((1, block_q, 1), q_index_dkv),
        pl.BlockSpec((1, block_q, 1), q_index_dkv),
    ]
    dkv_args = [q, k, v, g, lse, delta]
    if has_lens:
        dkv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_args.append(lens)
    if has_slopes:
        dkv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_args.append(slopes)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, q_off=q_off, sk=sk,
                          block_q=block_q,
                          block_k=block_k, nq=nq, banded=banded,
                          nsteps=nq_steps, has_lens=has_lens,
                          has_slopes=has_slopes),
        grid=(bh, nk, nq_steps),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_attention_dkv",
    )(*dkv_args)
    if kv_rep > 1:
        # per-q-head partials -> sum over each kv group (rows are contiguous)
        dk = dk.reshape(bh_kv, kv_rep, sk, d).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bh_kv, kv_rep, sk, d).sum(axis=1).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, lens, slopes, scale, causal, window, kv_rep, block_q,
           block_k, interpret):
    out, _ = _flash_fwd(q, k, v, lens, slopes, scale=scale, causal=causal,
                        window=window, kv_rep=kv_rep, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return out


def _flash_vjp_fwd(q, k, v, lens, slopes, scale, causal, window, kv_rep,
                   block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, lens, slopes, scale=scale, causal=causal,
                          window=window, kv_rep=kv_rep, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return out, (q, k, v, lens, slopes, out, lse)


def _flash_vjp_bwd(scale, causal, window, kv_rep, block_q, block_k, interpret,
                   res, g):
    dq, dk, dv = _flash_bwd(res, g, scale=scale, causal=causal, window=window,
                            kv_rep=kv_rep, block_q=block_q, block_k=block_k,
                            interpret=interpret)
    lens, slopes = res[3], res[4]
    dlens = None if lens is None else np.zeros(lens.shape, _float0)
    # ALiBi slopes are a fixed head geometry, not learned (flash-attn's
    # alibi_slopes contract) — zero cotangent
    dslopes = None if slopes is None else jnp.zeros_like(slopes)
    return dq, dk, dv, dlens, dslopes


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    window: int | None = None, kv_lens=None,
                    alibi_slopes=None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None):
    """q,k,v: [B, S, H, D] (reference flash_attention layout). GQA supported
    natively: K/V may carry fewer heads (H % H_kv == 0); the kernel reads kv
    row b//rep through the index map, so no repeated K/V is materialised.
    ``window``: causal sliding-window size (Mistral-style; token i attends
    to [i-window+1, i]) — the banded grid skips out-of-band tiles AND their
    DMAs, so long-sequence cost is O(S*window).
    ``kv_lens``: [B] int32 valid key lengths — the padded-varlen path (ref
    ``flash_attn_varlen`` capability): keys >= the row's length are masked
    in-kernel and fully-padded key blocks are skipped, with no O(S^2) mask
    tensor. NOTE query rows in the padding are NOT masked q-side: under
    causal+kv_lens a padded query row still attends every key < its row's
    klen, so its output is unspecified garbage — callers MUST mask those
    rows out of the loss (zero upstream cotangent), which is also what
    makes their grads exactly zero.
    ``alibi_slopes``: [H] (or [B, H]) positive ALiBi slopes m — the kernel
    adds ``-m * (q_pos - k_pos)`` to the scores, computed from iota IN the
    tile (the flash-attn ``alibi_slopes`` capability): no O(S^2) bias
    tensor exists, unlike the XLA additive-mask path. Slopes are fixed
    head geometry (not learned): their cotangent is zero."""
    b, s, h, d = q.shape
    sk = k.shape[1]
    h_kv = k.shape[2]
    if h % h_kv != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    kv_rep = h // h_kv
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if window is not None and kv_lens is not None and s != sk:
        # the banded grid's block-liveness pruning is computed from the
        # buffer-end offset; under klen-aligned decode positions it could
        # skip live tiles — refuse rather than silently drop attention
        raise NotImplementedError(
            "window + kv_lens with sq != sk (windowed decode against a "
            "padded cache) is not supported; trim the cache or use the "
            "paged decode kernel")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = scale if scale is not None else d ** -0.5

    def _fit(blk, n):
        # largest power-of-two divisor step down from the requested block:
        # a non-dividing block would pad the grid and the padded key
        # columns (k_idx in [sk, nk*bk)) pass the causal mask for late
        # query rows — garbage would enter the softmax
        blk = min(blk, n)
        while n % blk:
            blk //= 2
        return max(blk, 1)

    bq = _fit(block_q, s)
    bk = _fit(block_k, sk)

    # Non-128-divisible lengths would otherwise step the tile down to a
    # tiny divisor (s=1000 -> bq=8 — ~64x smaller MXU tiles than the
    # tuned default): pad to an aligned length and mask/slice the tail
    # instead. Padded KEY columns are masked causally (equal q/k padding
    # keeps q_off = 0, so every real row's pad columns sit strictly above
    # the diagonal) or by the kv_lens machinery (klen <= sk always masks
    # them; `_q_offset`'s klen-based alignment is invariant under k-only
    # padding). Padded QUERY rows compute junk that is sliced off — no
    # padded row is ever fully masked, so no NaN leaks into the bwd
    # matmuls via their zero cotangent. Skipped for windowed decode
    # (s != sk): masking pads there needs kv_lens, a combo the banded
    # grid refuses above.
    pad_q = pad_k = 0
    if (((bq < 128 and s > 128) or (bk < 128 and sk > 128))
            and not (window is not None and s != sk)):
        tq = min(block_q, 1 << max(7, s.bit_length() - 1))
        tk = min(block_k, 1 << max(7, sk.bit_length() - 1))
        if s == sk:
            t = max(tq, tk)           # one pad aligns both (powers of 2)
            pad_q = pad_k = (-s) % t
            if not causal and kv_lens is None:
                kv_lens = jnp.full((b,), sk, jnp.int32)
        else:
            # end-aligned query rows (decode): pad K only; bq keeps the
            # _fit value (decode sq is small and usually aligned). If sk
            # is already aligned (the trigger was a tiny bq) there is
            # nothing to pad — forcing kv_lens then would buy the lens
            # masking overhead for no tile improvement.
            pad_k = (-sk) % tk
            if pad_k and kv_lens is None:
                kv_lens = jnp.full((b,), sk, jnp.int32)
        if pad_q:
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        bq = _fit(block_q, s + pad_q)
        bk = _fit(block_k, sk + pad_k)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(-1, x.shape[1], d)

    lens = None
    if kv_lens is not None:
        # [B] -> [B*H, 1]: one scalar per q batch-head row
        lens = jnp.repeat(jnp.asarray(kv_lens, jnp.int32), h)[:, None]
    slopes = None
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        # [H] or [B, H] -> [B*H, 1]: one scalar per q batch-head row
        slopes = jnp.broadcast_to(slopes.reshape(-1, h), (b, h)
                                  ).reshape(-1)[:, None]
    out = _flash(to_bh(q), to_bh(k), to_bh(v), lens, slopes, scale, causal,
                 window, kv_rep, bq, bk, interpret)
    out = jnp.swapaxes(out.reshape(b, h, s + pad_q, d), 1, 2)
    return out[:, :s] if pad_q else out
