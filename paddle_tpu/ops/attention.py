"""Attention ops (ref: ``paddle/phi/kernels/fusion/flash_attn`` +
``python/paddle/nn/functional/flash_attention.py``).

Layout convention matches the reference flash_attention API: [B, S, H, D].
Dispatch order on TPU: Pallas flash kernel (paddle_tpu.ops.pallas) → fused
XLA path. The XLA path is itself MXU-friendly: two batched matmuls with a
fp32 softmax that XLA fuses into the surrounding computation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_NEG_INF = -2.3819763e38  # most-negative bf16-representable; avoids nan from -inf - -inf


def _use_pallas(q) -> bool:
    import os
    if os.environ.get("PADDLE_TPU_DISABLE_FLASH", "").lower() in ("1", "true", "yes"):
        return False  # escape hatch: force the XLA attention path
    from paddle_tpu.ops.pallas import mosaic_kernels_apply
    if not mosaic_kernels_apply():
        return False
    head_dim = q.shape[-1]
    seq = q.shape[1]
    return head_dim % 128 == 0 and seq % 128 == 0


def xla_attention(query, key, value, attn_mask=None, is_causal=False, scale=None,
                  dropout_p=0.0, training=True, rng=None, window=None,
                  kv_lens=None, alibi_slopes=None):
    """Reference-semantics attention in pure XLA. [B,S,H,D]. ``window``:
    causal sliding window (token i sees [i-window+1, i]), Mistral-style.
    ``kv_lens``: [B] valid key lengths (padded-varlen batches).
    ``alibi_slopes``: [H] or [B, H] positive slopes m — adds
    ``-m * (q_pos - k_pos)`` to the scores (this path materialises the
    bias; the Pallas kernel computes it in-tile)."""
    if window is not None and not is_causal:
        raise ValueError("window requires is_causal=True")
    b, sq, h, d = query.shape
    sk = key.shape[1]
    if kv_lens is not None:
        # [B] lengths -> [B,1,1,Sk] key-padding mask, merged with attn_mask
        pad = (jnp.arange(sk)[None, :] < jnp.asarray(kv_lens)[:, None])
        pad = pad[:, None, None, :]
        if attn_mask is None:
            attn_mask = pad
        elif attn_mask.dtype == jnp.bool_:
            attn_mask = attn_mask & pad
        else:
            attn_mask = jnp.where(pad, attn_mask, _NEG_INF)
    kv_heads = key.shape[2]
    if kv_heads != h:  # GQA: repeat KV heads
        rep = h // kv_heads
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)
    scale = scale if scale is not None else d ** -0.5
    q = jnp.swapaxes(query, 1, 2)  # [B,H,S,D]
    k = jnp.swapaxes(key, 1, 2)
    v = jnp.swapaxes(value, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    # query positions, shared by ALiBi and the causal/window masks: aligned
    # to the END of the key axis (KV-cache decode); with kv_lens AND
    # sq < sk (decode against a PADDED cache, flash-attn's cache_seqlens
    # form) the END is each row's valid length, so the result equals a
    # trimmed-cache solo call
    if kv_lens is not None and sq < sk:
        q_pos = (jnp.asarray(kv_lens, jnp.int32)[:, None] - sq
                 + jnp.arange(sq)[None, :])            # [B, Sq]
    else:
        q_pos = jnp.broadcast_to(jnp.arange(sq) + (sk - sq), (1, sq))
    k_pos = jnp.arange(sk)
    if alibi_slopes is not None:
        # fixed head geometry, not learned — matches the Pallas kernel's
        # zero-cotangent contract on every backend
        m_sl = jax.lax.stop_gradient(
            jnp.asarray(alibi_slopes, jnp.float32)).reshape(-1, h)  # [1|B,H]
        dist = (q_pos[:, :, None] - k_pos[None, None, :]).astype(jnp.float32)
        if not is_causal:
            dist = jnp.abs(dist)   # bidirectional ALiBi: symmetric decay
        scores = scores - m_sl[:, :, None, None] * dist[:, None]
    if is_causal or window is not None:
        keep = (q_pos[:, :, None] >= k_pos[None, None, :]) if is_causal \
            else jnp.ones((1, sq, sk), bool)
        if window is not None:
            keep &= (q_pos[:, :, None] - k_pos[None, None, :]) < window
        scores = jnp.where(keep[:, None], scores, _NEG_INF)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, _NEG_INF)
        else:
            scores = scores + attn_mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0 and training:
        if rng is None:
            from paddle_tpu.core.random import next_key
            rng = next_key()
        keep = jax.random.bernoulli(rng, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    probs = probs.astype(v.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, rng=None, scale=None,
                                 window=None, kv_lens=None, alibi_slopes=None):
    """Dispatch: Pallas flash (incl. the padded-varlen ``kv_lens`` path and
    in-tile ``alibi_slopes``) → XLA. An ARBITRARY ``attn_mask`` always
    takes the XLA path: a dense [.., Sq, Sk] mask has already materialised
    O(S^2) memory, so flash's advantage is gone — express padding as
    ``kv_lens`` and ALiBi as ``alibi_slopes`` to keep the fused kernel
    (ref: flash_attn's varlen/padded + alibi_slopes variants)."""
    h, kv = query.shape[2], key.shape[2]
    if (attn_mask is None and (dropout_p == 0.0 or not training)
            and _use_pallas(query)
            and h % kv == 0 and (window is None or is_causal)
            # windowed decode against a padded cache: the banded grid
            # refuses it, so it is not the kernel's shape
            and not (window is not None and kv_lens is not None
                     and query.shape[1] != key.shape[1])):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        # GQA handled inside the kernel (kv row = q row // rep) — no
        # materialised K/V repeat. A kernel that raises is an error, not
        # a reason to take the XLA path.
        return flash_attention(query, key, value, causal=is_causal, scale=scale,
                               window=window, kv_lens=kv_lens,
                               alibi_slopes=alibi_slopes)
    return xla_attention(query, key, value, attn_mask=attn_mask, is_causal=is_causal,
                         scale=scale, dropout_p=dropout_p, training=training, rng=rng,
                         window=window, kv_lens=kv_lens,
                         alibi_slopes=alibi_slopes)


flash_attention = scaled_dot_product_attention


# -- rotary embedding (ref: paddle.incubate.nn.functional.fused_rotary_position_embedding)

def resolve_rope_scaling(base, head_dim, scaling, seq_len=None,
                         max_position_embeddings=None, *,
                         allow_dynamic=True, cur_len=None):
    """The ONE place the rope_scaling math lives. Returns
    ``(base, position_divisor)`` for the reference rope_scaling dict
    (PaddleNLP/HF convention):
      {"type": "linear",  "factor": f} — position interpolation (pos / f)
      {"type": "ntk",     "factor": f} — base *= f^(d/(d-2)) (fixed NTK)
      {"type": "dynamic", "factor": f} — NTK base grows once the length
        exceeds the trained window. Fixed-shape decode paths carry the
        CURRENT length as traced data via ``cur_len`` (scalar or [B]
        per-row) — the returned base is then traced (per-row: [B] or
        [B, 1]); a decode path that passes neither raises
        (``allow_dynamic=False``) instead of silently mis-rotating.
        Per-step bases match HF generation semantics: earlier cache
        entries keep the base they were rotated with.
    """
    if not scaling:
        return base, 1.0
    kind, factor = scaling["type"], float(scaling["factor"])
    if kind == "linear":
        return base, factor
    if kind == "ntk":
        return base * factor ** (head_dim / (head_dim - 2)), 1.0
    if kind == "dynamic":
        if cur_len is not None:
            trained = max_position_embeddings
            if not trained:
                raise ValueError(
                    "dynamic rope_scaling with a traced cur_len needs "
                    "max_position_embeddings (the trained window)")
            alpha = jnp.maximum(
                factor * jnp.asarray(cur_len, jnp.float32) / trained
                - (factor - 1.0), 1.0)     # <= trained: unscaled (alpha 1)
            return base * alpha ** (head_dim / (head_dim - 2)), 1.0
        if not allow_dynamic:
            raise NotImplementedError(
                "dynamic-NTK rope_scaling needs the current sequence "
                "length; pass cur_len (traced) or use 'linear'/'ntk'")
        trained = max_position_embeddings or seq_len
        if seq_len is not None and seq_len > trained:
            alpha = factor * seq_len / trained - (factor - 1)  # HF formula
            base = base * alpha ** (head_dim / (head_dim - 2))
        return base, 1.0
    raise ValueError(f"unknown rope_scaling type {kind!r}")


def yarn_inv_freq(head_dim, base, scaling):
    """YaRN's per-pair inverse frequencies, [head_dim / 2] float32
    (``{"type": "yarn", "factor", "original_max_position_embeddings",
    "beta_fast", "beta_slow"}``, the published DeepSeek / Kimi form): the
    interpolated frequency ``f / factor`` and the plain ``f`` blended by a
    linear ramp over the pair index between the two correction dims
    (``yarn_find_correction_range``; ``yarn_linear_ramp_mask`` with its
    ``+0.001`` where the two bounds meet). A per-pair blend, so it is not
    a ``(base, divisor)`` of :func:`resolve_rope_scaling`."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    inv = base ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def correction_dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))),
               head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0 keeps the plain frequency (extrapolation), 1 interpolates
    return jnp.asarray(inv / factor * ramp + inv * (1.0 - ramp), jnp.float32)


def yarn_mscale(scaling, key="mscale_all_dim") -> float:
    """``0.1 * scaling[key] * ln(factor) + 1`` (1 at factor <= 1): YaRN's
    attention temperature. Latent attention multiplies its softmax scale by
    the square of the ``mscale_all_dim`` one."""
    factor = float(scaling["factor"])
    if factor <= 1.0:
        return 1.0
    return 0.1 * float(scaling.get(key, 1.0)) * math.log(factor) + 1.0


def rope_cos_sin(seq_len, head_dim, base=10000.0, dtype=jnp.float32, position_ids=None,
                 scaling=None, max_position_embeddings=None,
                 allow_dynamic=True, cur_len=None):
    """``scaling``: reference rope_scaling dict — see resolve_rope_scaling.
    ``cur_len``: traced current total length for dynamic scaling inside
    fixed-shape decode (the base becomes traced data, no recompile)."""
    base, pos_div = resolve_rope_scaling(
        base, head_dim, scaling, seq_len=seq_len,
        max_position_embeddings=max_position_embeddings,
        allow_dynamic=allow_dynamic, cur_len=cur_len)
    ar = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    pos = jnp.arange(seq_len, dtype=jnp.float32) if position_ids is None else position_ids
    if pos_div != 1.0:
        pos = pos / pos_div
    base = jnp.asarray(base, jnp.float32)
    if base.ndim == 0:
        freqs = jnp.outer(pos, 1.0 / (base ** ar))          # [S, D/2]
    else:
        # per-ROW dynamic base (ragged lengths): [B, S, D/2]
        inv_freq = 1.0 / (base[:, None] ** ar[None, :])
        freqs = pos[None, :, None] * inv_freq[:, None, :]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin):
    """x: [B,S,H,D]; cos/sin: [S, D/2] (shared) or [B, S, D/2] (per-row
    dynamic base). NeoX-style rotate-half (LLaMA)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    if cos.ndim == 3:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def apply_rope_interleaved(x, cos, sin):
    """GPT-J-style INTERLEAVED rotary: pairs are (even, odd) lanes
    ``(x[2i], x[2i+1])``, not the half-split. x: [B,S,H,D(rot)];
    cos/sin: [S, D/2]."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def fused_rotary_position_embedding(q, k, seq_len=None, base=10000.0, position_ids=None):
    s = seq_len or q.shape[1]
    cos, sin = rope_cos_sin(s, q.shape[-1], base=base, dtype=jnp.float32,
                            position_ids=position_ids)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


# -- fused residual chains (ref fused_bias_dropout_residual_layer_norm) -----

def fused_bias_dropout_residual_layer_norm(x, residual, bias=None, ln_scale=None,
                                           ln_bias=None, dropout_rate=0.0,
                                           epsilon=1e-5, training=True, rng=None):
    from paddle_tpu.nn import functional as F
    y = x if bias is None else x + bias
    y = F.dropout(y, dropout_rate, training=training, rng=rng)
    y = y + residual
    return F.layer_norm(y, y.shape[-1], ln_scale, ln_bias, epsilon)
