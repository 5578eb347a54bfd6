"""DeepSpeed-Ulysses-style sequence parallelism (ref capability:
``paddle.distributed.fleet`` sep-parallel / PaddleNLP sequence-parallel
attention).

Complement to ring attention (`ring_attention.py`): instead of rotating KV
blocks around the ring, one ``all_to_all`` re-shards activations from
sequence-sharded to head-sharded, runs ordinary full attention on a head
slice, and a second ``all_to_all`` restores sequence sharding. Two
collectives per layer, overlap-friendly on ICI, and the inner attention can
use the Pallas flash kernel unchanged — the better choice when
``num_heads >= sp`` and sequence length per chip is small.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax.lax import axis_size
from paddle_tpu.ops import attention as A


def ulysses_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                      scale=None, window=None, kv_lens=None, attn_mask=None,
                      attn_bias=None):
    """Attention over the full sequence with inputs sequence-sharded on
    ``axis_name``. [B, S_local, H, D] in and out; H must divide by the axis
    size. Call inside shard_map.

    ``kv_lens``: [B] global valid key lengths (padded varlen) — applied by
    the inner attention after the head-scatter, so the fused kernel's
    varlen path still runs. ``attn_mask``: [B, S, S] bool over GLOBAL
    positions, replicated (after the all_to_all every member holds the full
    sequence for its head slice, so the full mask is needed anyway).
    ``attn_bias``: [B|1, H_local|1, S, S] float ADDITIVE scores (T5
    relative bias, ALiBi) for THIS member's post-exchange head slice —
    ``make_ulysses_attention`` shards a global per-head bias over
    (tp, sp) so the slice lines up with the heads the all_to_all assigns."""
    sp = axis_size(axis_name)
    if q.shape[2] % sp != 0:
        raise ValueError(
            f"ulysses_attention: num_heads={q.shape[2]} must be divisible by "
            f"axis '{axis_name}' size {sp}")
    if k.shape[2] % sp != 0:
        # GQA with fewer KV heads than sp: replicate KV groups up to sp so
        # the head-scatter has something to split (standard Ulysses-GQA).
        # COST: the repeat materialises rep x the local KV before the
        # all_to_all (transient memory) and the exchange then moves
        # S_local*(sp-1)*D bytes/device instead of the no-GQA
        # S_local*kv_heads*(sp-1)/sp*D — an ICI multiplier of
        # rep = sp/kv_heads. There is no "repeat after the exchange"
        # alternative here: with kv_heads < sp the heads cannot be split sp
        # ways un-replicated, and an all_gather(seq) of the original KV
        # costs MORE ((sp-1)*S_local*kv_heads*D). When this bites, prefer
        # sequence_parallel="ring" (rotates un-replicated KV).
        if sp % k.shape[2] == 0:
            rep = sp // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        else:
            raise ValueError(
                f"ulysses_attention: num_key_value_heads={k.shape[2]} must "
                f"divide by (or into) axis '{axis_name}' size {sp}; use "
                "sequence_parallel='ring' for this head configuration")
    # seq-sharded -> head-sharded: gather sequence, scatter heads
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    mask = attn_mask[:, None] if attn_mask is not None else None  # [B,1,S,S]
    if attn_bias is not None:
        # merge additive bias with any bool mask: the XLA attention core
        # takes ONE attn_mask, so fold blocks into the bias as -inf
        bias = attn_bias.astype(jnp.float32)
        mask = bias if mask is None else jnp.where(mask, bias, -1e30)
    # window works unchanged: after the all_to_all the inner attention sees
    # the FULL sequence (global positions intact), so the sliding window is
    # exactly the single-device banded computation on a head slice
    out = A.scaled_dot_product_attention(qh, kh, vh, is_causal=causal,
                                         scale=scale, window=window,
                                         kv_lens=kv_lens, attn_mask=mask)
    # head-sharded -> seq-sharded
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_merge_partials(o, m, l, axis_name: str = "cp"):
    """Ulysses-style merge of per-shard online-softmax partials
    (context-parallel serving, ISSUE 18). Trailing-head layout like the
    ring variant: ``o`` [..., H, D] with ``m``/``l`` shaped
    ``o.shape[:-1]``.

    Instead of rotating whole triples around the ring, one tiled
    ``all_to_all`` re-shards them from partial-per-member to
    head-sharded — member j receives head slice j of ALL n partials,
    stacked along a leading axis in source-member (= global shard)
    order. Each member folds its slice 0..n-1 and an ``all_gather``
    restores the full head dim, so every member ends with the same
    bit-identical merged triple. Bytes moved per member:
    2·(n-1)/n · H·(D+2) floats — same order as the ring, but in one
    collective round instead of n-1. Requires H % n == 0."""
    n = axis_size(axis_name)
    if n == 1:
        return o, m, l
    ho, hm = o.ndim - 2, m.ndim - 1
    if o.shape[ho] % n != 0:
        raise ValueError(
            f"ulysses_merge_partials: heads={o.shape[ho]} must divide by "
            f"axis '{axis_name}' size {n}; use PT_CP_IMPL=ring")

    def split(x, ax):
        y = lax.all_to_all(x, axis_name, split_axis=ax, concat_axis=0,
                           tiled=True)
        return y.reshape((n,) + x.shape[:ax]
                         + (x.shape[ax] // n,) + x.shape[ax + 1:])

    from paddle_tpu.distributed.ring_attention import merge_partials
    o_s, m_s, l_s = split(o, ho), split(m, hm), split(l, hm)
    o_a, m_a, l_a = o_s[0], m_s[0], l_s[0]
    for g in range(1, n):
        o_a, m_a, l_a = merge_partials(o_a, m_a, l_a,
                                       o_s[g], m_s[g], l_s[g])
    o_a = lax.all_gather(o_a, axis_name, axis=ho, tiled=True)
    m_a = lax.all_gather(m_a, axis_name, axis=hm, tiled=True)
    l_a = lax.all_gather(l_a, axis_name, axis=hm, tiled=True)
    return o_a, m_a, l_a


def make_ulysses_attention(mesh, causal: bool = True, axis_name: str = "sp",
                           head_spec=None, batch_axes=("dp", "fsdp"),
                           window: int | None = None,
                           varlen: bool = False, masked: bool = False,
                           bias_shape=None, scale=None):
    """Bind ulysses_attention onto a HybridMesh via shard_map: takes/returns
    [B, S, H, D] arrays sequence-sharded over ``axis_name``; batch sharded
    over ``batch_axes``; ``head_spec="tp"`` composes with tensor
    parallelism (each tp member re-shards its own head slice over sp, so
    local heads must divide by sp * tp).
    ``varlen=True``: attend(q, k, v, kv_lens) with [B] key lengths.
    ``masked=True``: attend(..., attn_mask) with [B, S, S] bool (replicated
    over sp — the head-sharded inner attention needs the whole mask).
    ``bias_shape``: shape of a [B|1, H|1, S, S] ADDITIVE float bias passed
    as the last argument. A per-head bias is sharded over (tp, sp) on the
    head dim — tp-major, sp-minor, exactly the head range device
    (tp_j, sp_i) ends up computing after the all_to_all."""
    from jax import shard_map

    spec = P(batch_axes, axis_name, head_spec, None)
    in_specs = [spec, spec, spec]
    if varlen:
        in_specs.append(P(batch_axes))
    if masked:
        in_specs.append(P(batch_axes, None, None))
    if bias_shape is not None:
        from paddle_tpu.distributed.ring_attention import bias_spec
        in_specs.append(bias_spec(
            bias_shape,
            (head_spec, axis_name) if head_spec else (axis_name,),
            batch_axes=batch_axes, rows_axis=None))

    def fn(q, k, v, *extra):
        it = iter(extra)
        lens = next(it) if varlen else None
        mask = next(it) if masked else None
        bias = next(it) if bias_shape is not None else None
        return ulysses_attention(q, k, v, axis_name=axis_name, causal=causal,
                                 scale=scale, window=window, kv_lens=lens,
                                 attn_mask=mask, attn_bias=bias)

    return shard_map(fn, mesh=mesh.mesh, in_specs=tuple(in_specs),
                     out_specs=spec, check_vma=False)
