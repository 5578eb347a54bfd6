"""Olmo-Hybrid (allenai ``Olmo-Hybrid-7B``, ``model_type: olmo_hybrid``): a
decoder whose layers are, by ``layer_types``, either softmax attention or
the gated delta rule (Gated DeltaNet), three linear layers to one full.

What differs from :mod:`paddle_tpu.models.llama`:

* the **block** is Olmo 2/3's: no norm before a branch, each branch through
  its own RMSNorm before its add (``x + norm(Attn(x))``, ``x +
  norm(MLP(x))``; the norms carry the sandwich layer's names,
  ``input_layernorm_2`` and ``post_attention_layernorm_2``, which
  ``models/paged.py``'s ``_residual`` already knows);
* a **full layer** norms q and k over the whole projection before RoPE
  (``q_norm``, ``k_norm`` on the attention module);
* a **linear layer** keeps no K/V: :class:`GatedDeltaMixer` carries a
  recurrent state ``S`` in ``R^{d_k x d_v}`` a head (float32) and the last
  ``linear_conv_kernel_dim - 1`` inputs of its depthwise convolution
  (bfloat16). Served, that state lives a slot beside the paged K/V pools
  of the full layers (``models/paged.py``: ``PagedKVCache.states``).

The mixer's body is written once (:meth:`GatedDeltaMixer.mix`) and is what
the dense forward here and the three paged forwards call: a whole prompt
from a zero state, a chunk from an incoming state, one token a slot.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.module import Module
from paddle_tpu.models.llama import (LlamaAttention, LlamaConfig,
                                     LlamaForCausalLM, LlamaMLP, LlamaModel,
                                     LlamaRMSNorm)
from paddle_tpu.models.paged import FULL_LAYER as FULL
from paddle_tpu.models.paged import LINEAR_LAYER as LINEAR
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops import attention as A
from paddle_tpu.ops.pallas.gated_delta import (gated_delta_chunk,
                                               gated_delta_step)
from paddle_tpu.quantization import wo_matmul

_PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclass
class OlmoHybridConfig(LlamaConfig):
    """The published keys under their own names. ``layer_types`` may be
    longer than ``num_hidden_layers`` (a model cut in depth keeps the
    published list): layer ``i`` is ``layer_types[i]``."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    layer_types: tuple = _PERIOD * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        kinds = self.layer_types[:self.num_hidden_layers]
        if len(kinds) < self.num_hidden_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name each of the {self.num_hidden_layers} "
                f"layers as {LINEAR!r} or {FULL!r}, got {self.layer_types}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError(
                "linear_num_key_heads != linear_num_value_heads (grouped "
                "value heads) is not built")

    @property
    def kinds(self) -> tuple:
        return self.layer_types[:self.num_hidden_layers]

    @staticmethod
    def tiny(**kw):
        """One period at the published head ratios (d_v = 2 d_k, as many
        linear heads as attention heads)."""
        return OlmoHybridConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=512,
            layer_types=_PERIOD, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, dtype=jnp.float32, remat=False), **kw})


def _l2norm(x, eps=1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class GatedDeltaMixer(Module):
    """One linear layer's token mixer (the equations of ISSUE 35)."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        e, h = cfg.hidden_size, cfg.linear_num_key_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.num_heads, self.dk, self.dv = h, dk, dv
        self.kernel = cfg.linear_conv_kernel_dim
        self.neg_eigval = bool(cfg.linear_allow_neg_eigval)
        self.eps = cfg.rms_norm_eps
        init = I.Normal(0.0, cfg.initializer_range)
        chans = h * (2 * dk + dv)
        # [q | k | v | z] then [b | a]: one matmul each
        self.qkvz_proj = init((e, chans + h * dv), cfg.dtype)
        self.ba_proj = init((e, 2 * h), cfg.dtype)
        # PyTorch's Conv1d default at fan-in ``kernel``: uniform, not the
        # initializer_range of the matrices
        bound = self.kernel ** -0.5
        self.conv_weight = I.Uniform(-bound, bound)((self.kernel, chans),
                                                    cfg.dtype)
        self.o_proj = init((h * dv, e), cfg.dtype)
        self.o_norm = jnp.ones((dv,), cfg.dtype)
        # the public Gated DeltaNet draws: A uniform on (0, 16), dt
        # log-uniform on (1e-3, 1e-1), kept as the inverse softplus of dt
        from paddle_tpu.core.random import next_key
        ka, kd = jax.random.split(next_key())
        self.A_log = jnp.log(jax.random.uniform(
            ka, (h,), jnp.float32, 1e-3, 16.0))
        dt = jnp.exp(jax.random.uniform(
            kd, (h,), jnp.float32, np.log(1e-3), np.log(1e-1)))
        self.dt_bias = dt + jnp.log(-jnp.expm1(-dt))
        self.set_pspec("qkvz_proj", P(None, "tp"))
        self.set_pspec("o_proj", P("tp", None))

    def mix(self, x, state, conv, lens):
        """x [B, T, hidden]; state [B, H, d_k, d_v] float32 and conv [B,
        K - 1, channels] as they stood before the row's first token; lens
        [B] live tokens a row (a token past it changes no state) ->
        (y [B, T, hidden], state, conv) after each row's live tokens.
        ``T`` = 1 is the decode step, the rule one token a slot."""
        b, t, _ = x.shape
        h, dk, dv, kw = self.num_heads, self.dk, self.dv, self.kernel
        chans = h * (2 * dk + dv)
        uz = wo_matmul(x, self.qkvz_proj)
        u, z = uz[..., :chans], uz[..., chans:]
        ba = wo_matmul(x, self.ba_proj).astype(jnp.float32)
        # depthwise causal convolution over [the last K-1 inputs | the row]
        ext = jnp.concatenate([conv.astype(u.dtype), u], axis=1)
        w = self.conv_weight.astype(jnp.float32)
        c = sum(ext[:, i:i + t].astype(jnp.float32) * w[i]
                for i in range(kw))
        c = jax.nn.silu(c)
        # the inputs of the row's last K-1 live tokens, for the next call
        conv = jax.vmap(lambda e_, n: jax.lax.dynamic_slice_in_dim(
            e_, n, kw - 1, axis=0))(ext, lens).astype(conv.dtype)
        q, k, v = jnp.split(c, [h * dk, 2 * h * dk], axis=-1)
        q = (_l2norm(q.reshape(b, t, h, dk)) * dk ** -0.5).astype(x.dtype)
        k = _l2norm(k.reshape(b, t, h, dk)).astype(x.dtype)
        v = v.reshape(b, t, h, dv).astype(x.dtype)
        beta = jax.nn.sigmoid(ba[..., :h])
        if self.neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(self.A_log) * jax.nn.softplus(ba[..., h:]
                                                    + self.dt_bias)
        if t == 1:
            o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                        beta[:, 0], state, lens > 0)
            o = o[:, None]
        else:
            o, state = gated_delta_chunk(q, k, v, g, beta, state, lens)
        # RMSNorm of each head's output, gated by silu(z)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps) * self.o_norm.astype(jnp.float32)
        o = o * jax.nn.silu(z.reshape(b, t, h, dv).astype(jnp.float32))
        y = wo_matmul(o.reshape(b, t, h * dv).astype(x.dtype), self.o_proj)
        return y, state, conv

    def __call__(self, x):
        """A whole row from a zero state (the dense forward)."""
        b, t, _ = x.shape
        state = jnp.zeros((b, self.num_heads, self.dk, self.dv), jnp.float32)
        conv = jnp.zeros((b, self.kernel - 1, self.conv_weight.shape[1]),
                         x.dtype)
        return self.mix(x, state, conv, jnp.full((b,), t, jnp.int32))[0]


class OlmoHybridAttention(LlamaAttention):
    """LLaMA's attention with an RMSNorm of q and of k over the whole
    projection before RoPE."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__(cfg)
        d = self.head_dim
        self.q_norm = LlamaRMSNorm(self.num_heads * d, cfg.rms_norm_eps,
                                   cfg.dtype)
        self.k_norm = LlamaRMSNorm(self.num_kv_heads * d, cfg.rms_norm_eps,
                                   cfg.dtype)

    def __call__(self, x, cos, sin, attn_mask=None):
        b, s, _ = x.shape
        nh, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q, k, v = jnp.split(wo_matmul(x, self.qkv_proj),
                            [nh * d, (nh + nkv) * d], axis=-1)
        q = A.apply_rope(self.q_norm(q).reshape(b, s, nh, d), cos, sin)
        k = A.apply_rope(self.k_norm(k).reshape(b, s, nkv, d), cos, sin)
        out = self._attend(q, k, v.reshape(b, s, nkv, d), attn_mask)
        return wo_matmul(out.reshape(b, s, nh * d), self.o_proj)


class OlmoHybridDecoderLayer(Module):
    """``x + norm(mixer(x))`` then ``x + norm(MLP(x))``; the mixer is
    ``self_attn`` (full) or ``linear_attn`` (the gated delta rule)."""

    def __init__(self, cfg: OlmoHybridConfig, kind: str):
        super().__init__()
        norm = lambda: LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                    cfg.dtype)
        if kind == LINEAR:
            self.linear_attn = GatedDeltaMixer(cfg)
        else:
            self.self_attn = OlmoHybridAttention(cfg)
        self.mlp = LlamaMLP(cfg)
        self.input_layernorm_2 = norm()
        self.post_attention_layernorm_2 = norm()

    def __call__(self, x, cos, sin, attn_mask=None):
        with jax.named_scope("attention"):
            if hasattr(self, "linear_attn"):
                branch = self.linear_attn(x)
            else:
                branch = self.self_attn(x, cos, sin, attn_mask)
            x = x + self.input_layernorm_2(branch)
        with jax.named_scope("mlp"):
            return x + self.post_attention_layernorm_2(self.mlp(x))


class OlmoHybridModel(LlamaModel):
    def __init__(self, cfg: OlmoHybridConfig):
        if cfg.scan_layers:
            raise NotImplementedError(
                "OlmoHybridModel's layers are of two kinds; scan_layers is "
                "not supported")
        Module.__init__(self)
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = init((cfg.vocab_size, cfg.hidden_size), cfg.dtype)
        self.set_pspec("embed_tokens", P("tp", None))
        self.layers = [OlmoHybridDecoderLayer(cfg, kind)
                       for kind in cfg.kinds]
        self.layers_stacked = None
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)


class OlmoHybridForCausalLM(LlamaForCausalLM):
    backbone = OlmoHybridModel
