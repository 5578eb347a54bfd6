"""Quantized serving subsystem (ISSUE 17).

The engine-facing entry points of the LLM.int8()/SmoothQuant recipe
(PAPERS.md) over the paged serving stack:

* :func:`quantize_for_serving` — structure-agnostic weight-only
  quantization (int8 / packed int4 / GPTQ) of any model the paged
  forwards can drive: Llama/Qwen dense layers ride the existing
  ``QuantizedWeight`` + ``wo_matmul`` dispatch from ``quantization.py``;
  Mixtral/Qwen2-MoE/MoE expert stacks get :class:`QuantizedExpertStack`
  (a 3-D [E, K, N] variant that ``distributed.moe`` dequantizes on the
  fly inside the jitted forward).

* :func:`smooth_for_serving` — SmoothQuant-style per-channel outlier
  migration: activation scale is folded OUT of the RMSNorm weight and
  INTO the adjacent projection (norm/s ↔ W·s), so the product is exact
  while the quantized weight distribution flattens. With ``calib_ids``
  the migration follows measured activation absmax (dense Llama models
  only — the capture forward is structure-specific); without, a
  weight-balancing heuristic that equalises per-in-channel weight
  magnitude. ``o_proj``/``down_proj`` are NOT smoothed: they have no
  preceding norm to fold into (their input is an attention/SiLU
  product), so migration has nowhere to hide the scale.

* quality instrumentation — quantization error is measured, never
  assumed: :func:`quant_quality` reports logit MSE and greedy
  match-rate against a reference model and publishes both as
  ``serving_quant_*`` gauges next to the throughput metrics.

The int8 KV-cache leg lives in ``models/paged.py`` (quantize-on-write /
dequantize-on-read around the block pools — ``PagedKVCache.init(...,
kv_dtype="int8")``) and is wired through
``LLMEngine(kv_dtype="int8")``; this module only hosts the weight side
and the shared quality/capacity instruments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.paged import _backbone, is_moe_model
from paddle_tpu.observability.metrics import METRICS
from paddle_tpu.quantization import (QuantizedWeight, _capture_calib,
                                     quantize_llama_weights, weight_quantize)

__all__ = [
    "QuantizedExpertStack", "expert_stack_quantize",
    "quantize_for_serving", "smooth_for_serving", "quant_quality",
    "quantized_weight_bytes",
]

# ---- instruments (published by quantize_for_serving / quant_quality) -------
_Q_BITS = METRICS.gauge(
    "serving_quant_weight_bits",
    "Weight-only quantization bit-width of the last model passed through "
    "quantize_for_serving (0 = unquantized)")
_Q_LAYERS = METRICS.gauge(
    "serving_quant_layers",
    "Decoder layers whose projections were converted to quantized weights "
    "by the last quantize_for_serving call")
_Q_WEIGHT_BYTES = METRICS.gauge(
    "serving_quant_weight_bytes",
    "HBM bytes of the quantized projection/head weights (codes + scales) "
    "after the last quantize_for_serving call")
_Q_SMOOTHED = METRICS.gauge(
    "serving_quant_smoothed",
    "1 when SmoothQuant-style activation smoothing was folded into the "
    "weights before quantization, else 0")
_Q_MSE = METRICS.gauge(
    "serving_quant_logit_mse",
    "Mean squared error between reference and quantized logits from the "
    "last quant_quality probe")
_Q_MATCH = METRICS.gauge(
    "serving_quant_greedy_match_rate",
    "Fraction of positions whose argmax token matches the reference in "
    "the last quant_quality probe")


# ---- 3-D expert stacks ------------------------------------------------------

class QuantizedExpertStack:
    """int8/int4 expert weight stack + per-(expert, out-channel) scale.

    The MoE analogue of :class:`~paddle_tpu.quantization.QuantizedWeight`:
    original stack [E, K, N] (expert, in, out). int8 stores codes as
    [E, K, N] int8; int4 packs two 4-bit values per byte along K ->
    [E, ceil(K/2), N] (low nibble = even k). ``distributed.moe`` detects
    the ``dequantize`` attribute and rebuilds the compute-dtype stack on
    the fly inside the jitted forward, so HBM holds 1 (or 0.5)
    byte/param for the dominant expert weights.
    """

    def __init__(self, q, scale, bits: int, k: int):
        self.q = q
        self.scale = scale          # [E, 1, N] fp32
        self.bits = int(bits)
        self.k = int(k)

    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.k)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, aux[0], aux[1])

    @property
    def shape(self):
        return (self.q.shape[0], self.k, self.q.shape[-1])

    def nbytes(self):
        return self.q.size * self.q.dtype.itemsize + self.scale.size * 4

    def unpack(self):
        """int8 [E, K, N] values (sign-extended nibbles for int4)."""
        if self.bits == 8:
            return self.q
        packed = self.q
        low = jnp.right_shift(jnp.left_shift(packed, 4), 4)  # sign-extends
        high = jnp.right_shift(packed, 4)
        e, _, n = packed.shape
        out = jnp.stack([low, high], axis=2).reshape(e, -1, n)
        return out[:, : self.k]

    def dequantize(self, dtype=jnp.float32):
        return (self.unpack().astype(jnp.float32) * self.scale).astype(dtype)


jax.tree_util.register_pytree_node(
    QuantizedExpertStack,
    lambda t: t.tree_flatten(),
    QuantizedExpertStack.tree_unflatten)


def expert_stack_quantize(w, algo: str = "weight_only_int8"):
    """RTN per-(expert, out-channel) symmetric quantization of a
    [E, K, N] expert stack."""
    bits = {"weight_only_int8": 8, "weight_only_int4": 4}[algo]
    e, k, n = w.shape
    qmax = 2.0 ** (bits - 1) - 1
    f = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f), axis=1, keepdims=True),
                        1e-8) / qmax
    q = jnp.clip(jnp.round(f / scale), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        if k % 2:
            q = jnp.concatenate(
                [q, jnp.zeros((e, 1, n), q.dtype)], axis=1)
        low = q[:, 0::2]
        high = q[:, 1::2]
        q = ((high.astype(jnp.int32) << 4)
             | (low.astype(jnp.int32) & 0xF)).astype(jnp.int8)
    return QuantizedExpertStack(q, scale, bits, k)


# ---- SmoothQuant-style activation smoothing ---------------------------------

def _fold(norm, s, *targets):
    """Exact migration norm/s ↔ W·s: the norm output shrinks by s per
    channel and every consumer of that output grows its matching input
    rows by s, so each product is unchanged (up to f32 rounding)."""
    w = norm.weight
    norm.weight = (w.astype(jnp.float32) / s).astype(w.dtype)
    out = []
    for t in targets:
        if t is None:
            out.append(None)
        elif t.ndim == 3:       # [E, K, N] expert stack
            out.append((t.astype(jnp.float32) * s[None, :, None])
                       .astype(t.dtype))
        else:                   # [K, N] projection (or [K, E] router)
            out.append((t.astype(jnp.float32) * s[:, None]).astype(t.dtype))
    return out


def _smooth_scale(a_x, w, alpha):
    """s = a_x^alpha / a_w^(1-alpha) per in-channel, clipped to keep the
    fold numerically sane. ``w``: 2-D [K, N] or 3-D [E, K, N]."""
    f = jnp.abs(w.astype(jnp.float32))
    red = (0, 2) if f.ndim == 3 else (1,)
    a_w = jnp.maximum(jnp.max(f, axis=red), 1e-8)
    s = (a_x ** alpha) / (a_w ** (1.0 - alpha))
    return jnp.clip(s, 1e-3, 1e3)


def smooth_for_serving(model, *, calib_ids=None, alpha: float = 0.5):
    """Fold SmoothQuant-style per-channel smoothing into the weights
    IN PLACE (call BEFORE :func:`quantize_for_serving`; the bf16 model
    computes the same function modulo float rounding).

    Two foldable seams per decoder layer:
      input_layernorm          ↔ qkv_proj
      post_attention_layernorm ↔ gate_up (dense MLP, every MoE expert,
                                 AND the router gate — all consume the
                                 same normed activations)

    ``calib_ids`` [B, S] drives measured activation absmax (dense
    Llama-family only); None uses a_x = 1, i.e. pure weight-magnitude
    balancing, valid for every structure.
    """
    bb = _backbone(model)
    stats = None
    if calib_ids is not None:
        if is_moe_model(model) or not hasattr(model, "model"):
            raise NotImplementedError(
                "activation-calibrated smoothing needs the dense "
                "Llama-family capture forward; smooth MoE models without "
                "calib_ids (weight-balancing heuristic)")
        stats = _capture_calib(model, jnp.asarray(calib_ids))

    def a_x(li, key, k):
        if stats is None:
            return jnp.ones((k,), jnp.float32)
        act = stats[li][key]                        # [M, K] float32
        return jnp.maximum(jnp.asarray(np.abs(act).max(axis=0)), 1e-8)

    for li, lyr in enumerate(bb.layers):
        att = lyr.self_attn
        h = att.qkv_proj.shape[0]
        s = _smooth_scale(a_x(li, "qkv", h), att.qkv_proj, alpha)
        (att.qkv_proj,) = _fold(lyr.input_layernorm, s, att.qkv_proj)

        blk = lyr.moe if hasattr(lyr, "moe") else lyr.mlp
        if hasattr(blk, "experts"):
            gu = blk.experts.gate_up                # [E, H, 2I]
            s = _smooth_scale(a_x(li, "gate_up", gu.shape[1]), gu, alpha)
            # the router reads the SAME normed activations — scale it
            # too or routing decisions would shift under smoothing
            blk.experts.gate_up, blk.gate_w = _fold(
                lyr.post_attention_layernorm, s, gu, blk.gate_w)
        else:
            gu = blk.gate_up_proj
            s = _smooth_scale(a_x(li, "gate_up", gu.shape[0]), gu, alpha)
            (blk.gate_up_proj,) = _fold(
                lyr.post_attention_layernorm, s, gu)
    model._smoothed = True
    return model


# ---- engine-facing entry point ----------------------------------------------

def quantized_weight_bytes(model) -> int:
    """HBM bytes of the quantized projections/head (codes + scales)."""
    total = 0
    for lyr in _backbone(model).layers:
        for obj in (lyr.self_attn,
                    lyr.moe if hasattr(lyr, "moe") else lyr.mlp,
                    getattr(lyr, "moe", None) and lyr.moe.experts):
            for v in (vars(obj).values() if obj is not None else ()):
                if isinstance(v, (QuantizedWeight, QuantizedExpertStack)):
                    total += v.nbytes()
    head = getattr(model, "lm_head", None)
    if isinstance(head, QuantizedWeight):
        total += head.nbytes()
    return total


def quantize_for_serving(model, algo: str = "weight_only_int8", *,
                         calib_ids=None, smooth: bool = False,
                         smooth_alpha: float = 0.5,
                         percdamp: float = 0.01):
    """Weight-only quantize a model IN PLACE for the paged serving stack.

    Structure-agnostic over the ``models/paged.py`` adapter seam: dense
    Llama-family projections (qkv/o/gate_up/down + untied lm_head)
    become :class:`~paddle_tpu.quantization.QuantizedWeight` (the paged
    forwards already dispatch through ``wo_matmul``); MoE expert stacks
    become :class:`QuantizedExpertStack` (dequantized on the fly by
    ``distributed.moe``); the fp32 router gate is NEVER quantized
    (routing decisions are cheap and precision-critical).

    ``algo``: weight_only_int8 | weight_only_int4 | gptq_int8 |
    gptq_int4 (GPTQ needs ``calib_ids`` and a dense Llama-family model —
    the Hessian capture forward is structure-specific). ``smooth=True``
    folds :func:`smooth_for_serving` in first.
    """
    bb = _backbone(model)
    if any(getattr(lyr.self_attn, "fp8_meta", None) is not None
           for lyr in bb.layers):
        raise ValueError(
            "weight-only quantization and the fp8 training path are "
            "mutually exclusive; rebuild the model with fp8=False")
    gptq = algo.startswith("gptq")
    bits = 4 if algo.endswith("int4") else 8
    rtn = f"weight_only_int{bits}"
    moe = is_moe_model(model)

    if smooth:
        smooth_for_serving(model, calib_ids=calib_ids, alpha=smooth_alpha)

    if gptq:
        if moe or not hasattr(model, "model"):
            raise NotImplementedError(
                "GPTQ for serving supports dense Llama-family models "
                "only (the calibration capture forward is "
                "structure-specific); use weight_only_int8/int4")
        quantize_llama_weights(model, algo, calib_ids=calib_ids,
                               percdamp=percdamp)
    else:
        for lyr in bb.layers:
            att = lyr.self_attn
            att.qkv_proj = weight_quantize(att.qkv_proj, rtn)
            att.o_proj = weight_quantize(att.o_proj, rtn)
            blk = lyr.moe if hasattr(lyr, "moe") else lyr.mlp
            if hasattr(blk, "experts"):
                ex = blk.experts
                ex.gate_up = expert_stack_quantize(ex.gate_up, rtn)
                ex.down = expert_stack_quantize(ex.down, rtn)
            else:
                blk.gate_up_proj = weight_quantize(blk.gate_up_proj, rtn)
                blk.down_proj = weight_quantize(blk.down_proj, rtn)
        if getattr(model, "lm_head", None) is not None:
            model.lm_head = weight_quantize(model.lm_head, rtn)

    # roofline/geometry read these back (engine _geom closure)
    model._wo_bits = bits
    _Q_BITS.set(bits)
    _Q_LAYERS.set(len(bb.layers))
    _Q_SMOOTHED.set(1 if getattr(model, "_smoothed", False) else 0)
    try:
        _Q_WEIGHT_BYTES.set(quantized_weight_bytes(model))
    except Exception:
        pass                     # exotic structures: gauge is best-effort
    return model


# ---- quality instrumentation ------------------------------------------------

def quant_quality(ref_logits, q_logits) -> dict:
    """Logit MSE + greedy match-rate of quantized vs reference logits
    (any matching [..., V] shapes). Publishes both gauges and returns
    ``{"logit_mse", "greedy_match_rate"}``."""
    ref = np.asarray(ref_logits, np.float32)
    q = np.asarray(q_logits, np.float32)
    if ref.shape != q.shape:
        raise ValueError(f"shape mismatch {ref.shape} vs {q.shape}")
    mse = float(np.mean((ref - q) ** 2))
    match = float(np.mean(ref.argmax(-1) == q.argmax(-1)))
    _Q_MSE.set(mse)
    _Q_MATCH.set(match)
    return {"logit_mse": mse, "greedy_match_rate": match}
