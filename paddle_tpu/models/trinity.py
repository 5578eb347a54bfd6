"""Trinity (arcee-ai ``Trinity-Mini`` / ``Trinity-Nano``, ``model_type:
afmoe``): a sandwich-norm decoder whose layers are of two kinds, window
and global attention, over sigmoid-routed experts. No bias anywhere.

    h = E[token] * sqrt(hidden)                      (``mup_enabled``)
    h = h + N2(Attn(N1(h)));  h = h + N4(F(N3(h)))   four RMSNorms a block

**Attention.** ``q = x Wq`` (H heads of ``head_dim``, which is NOT hidden /
H), ``k = x Wk``, ``v = x Wv`` (H_kv heads), ``g = x Wg`` (H x head_dim
wide); q and k through an RMSNorm over each head's dims (one gain vector
for q, one for k). A ``sliding_attention`` layer rotates q and k (rope over
the whole head, halves paired) and position ``i`` attends ``j <= i`` with
``i - j < sliding_window``; a ``full_attention`` layer carries NO positional
encoding and attends every ``j <= i``. ``o = softmax(q k^T / sqrt(d)) v``,
``o = o * sigmoid(g)`` elementwise, ``y = o Wo``. ``layer_types`` names each
layer's kind (published: every ``global_attn_every_n_layers``-th is full).

**F** is a SwiGLU MLP in the first ``num_dense_layers`` layers and the
expert layer in every later one: ``Shared(u) + sum_{e in top-k} w_e
Expert_e(u)``, the gate ``distributed.moe.sigmoid_bias_gate``: scores
``sigmoid(u Wr)`` in float32, the ``num_experts_per_tok`` experts of largest
score PLUS the expert bias (a buffer), their weights the UNBIASED scores
over their sum (``route_norm``) times ``route_scale``. The layer holds every
expert (``MoELayer(held=None)``).

Served, the two kinds of layer live in two block spaces of one paged cache
(``models/paged.py``: ``window_space_layers``): a window layer's pools hold
O(window) blocks a row, a full layer's O(length). The fused ``qkv_proj``,
``q_norm`` / ``k_norm``, ``gate_proj`` and ``use_rope`` are what the paged
forwards' K/V body reads of a layer.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Module
from paddle_tpu.distributed.moe import MoELayer
from paddle_tpu.models.llama import LlamaConfig, LlamaMLP, LlamaRMSNorm
from paddle_tpu.models.paged import FULL_LAYER, WINDOW_LAYER
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops import attention as A
from paddle_tpu.ops import fused_rms_norm
from paddle_tpu.quantization import wo_matmul


@dataclass
class TrinityConfig(LlamaConfig):
    """The published keys under their own names (Trinity-Mini's values)."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    sliding_window: int | None = 2048
    global_attn_every_n_layers: int = 4
    # one name a layer; None: every ``global_attn_every_n_layers``-th full
    layer_types: tuple | None = None
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    mup_enabled: bool = True
    remat: bool = False

    def __post_init__(self):
        n, every = self.num_hidden_layers, self.global_attn_every_n_layers
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL_LAYER if (i + 1) % every == 0 else WINDOW_LAYER
                for i in range(n))
        self.layer_types = tuple(self.layer_types)
        for why in (
                (self.n_group, self.topk_group) != (1, 1) and
                f"group-limited routing (n_group {self.n_group}, topk_group "
                f"{self.topk_group})",
                self.score_func != "sigmoid" and
                f"the {self.score_func!r} router score",
                self.rope_scaling is not None and
                f"rope_scaling {self.rope_scaling!r}",
                (len(self.layer_types) != n or set(self.layer_types)
                 - {FULL_LAYER, WINDOW_LAYER}) and
                f"layer_types {self.layer_types} for {n} layers",
                WINDOW_LAYER in self.layer_types and not self.sliding_window
                and "window layers and no sliding_window"):
            if why:
                raise NotImplementedError(f"Trinity with {why} is not built")

    @staticmethod
    def tiny(**kw):
        """The published shape at toy widths: a head wider than hidden /
        heads, 8 query heads to 2 K/V heads, a window of 32, one dense layer
        and then a period of three window layers to one full, 16 experts of
        which 4 a token."""
        return TrinityConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_hidden_layers=5,
            num_attention_heads=8, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=4096, sliding_window=32,
            layer_types=(WINDOW_LAYER, WINDOW_LAYER, FULL_LAYER,
                         WINDOW_LAYER, WINDOW_LAYER),
            num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
            dtype=jnp.float32), **kw})


class HeadRMSNorm(Module):
    """RMSNorm over each head's dims of a projection laid out ``[...,
    heads x head_dim]``, one gain vector for every head."""

    def __init__(self, head_dim, eps, dtype):
        super().__init__()
        self.weight = jnp.ones((head_dim,), dtype)
        self.eps, self.head_dim = eps, head_dim

    def __call__(self, x):
        heads = x.reshape(*x.shape[:-1], -1, self.head_dim)
        return fused_rms_norm(heads, self.weight, self.eps).reshape(x.shape)


class TrinityAttention(Module):
    """What ``models/paged.py``'s K/V body reads: ``qkv_proj`` ([q | k |
    v] columns), ``q_norm`` / ``k_norm``, ``use_rope``, ``gate_proj``,
    ``o_proj``; ``window`` is the layer's own (None: a full layer)."""

    def __init__(self, cfg: TrinityConfig, kind: str):
        super().__init__()
        e, nh, nkv, d = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        self.num_heads, self.num_kv_heads, self.head_dim = nh, nkv, d
        self.window = cfg.sliding_window if kind == WINDOW_LAYER else None
        self.use_rope = kind == WINDOW_LAYER
        self.rope_theta = float(cfg.rope_theta)
        init = I.Normal(0.0, cfg.initializer_range)
        self.qkv_proj = init((e, (nh + 2 * nkv) * d), cfg.dtype)
        self.qkv_bias = None
        self.q_norm = HeadRMSNorm(d, cfg.rms_norm_eps, cfg.dtype)
        self.k_norm = HeadRMSNorm(d, cfg.rms_norm_eps, cfg.dtype)
        self.gate_proj = init((e, nh * d), cfg.dtype)
        self.o_proj = init((nh * d, e), cfg.dtype)

    def __call__(self, x):
        """Causal over the whole of ``x`` [B, S, E], the mask from the
        positions."""
        b, s, _ = x.shape
        nh, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q, k, v = jnp.split(wo_matmul(x, self.qkv_proj),
                            [nh * d, (nh + nkv) * d], axis=-1)
        q = self.q_norm(q).reshape(b, s, nh, d)
        k = self.k_norm(k).reshape(b, s, nkv, d)
        v = v.reshape(b, s, nkv, d)
        if self.use_rope:
            cos, sin = A.rope_cos_sin(s, d, base=self.rope_theta)
            q, k = A.apply_rope(q, cos, sin), A.apply_rope(k, cos, sin)
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        keep = j <= i
        if self.window is not None:
            keep &= i - j < self.window
        o = A.xla_attention(q, k, v, attn_mask=keep[None, None])
        o = o.reshape(b, s, nh * d) * jax.nn.sigmoid(
            wo_matmul(x, self.gate_proj).astype(jnp.float32)).astype(x.dtype)
        return wo_matmul(o, self.o_proj)


class TrinityMoE(Module):
    """The expert layer: the shared expert for every token plus the routed
    sum over all the layer's experts. -> (y, counts): ``counts`` int32 [2],
    the (token, expert) pairs routed and the experts that got at least one
    (``routed_pairs``, ``experts_hit`` on the serving spans)."""

    counts_routed = True      # ``models.paged.counts_routed``

    def __init__(self, cfg: TrinityConfig):
        super().__init__()
        self.moe = MoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            k=cfg.num_experts_per_tok, capacity_factor=None, dtype=cfg.dtype,
            norm_topk_prob=cfg.route_norm, router="sigmoid_bias",
            routed_scale=cfg.route_scale, held=None)
        self.shared = None
        if cfg.num_shared_experts:
            self.shared = LlamaMLP(replace(
                cfg, intermediate_size=(cfg.num_shared_experts
                                        * cfg.moe_intermediate_size)))

    def __call__(self, x, live=None):
        """``live`` [B, S] bool: False a padding token, routed nowhere."""
        y, _, m = self.moe(x, return_metrics=True, live=live)
        if self.shared is not None:
            y = y + self.shared(x)
        return y, jnp.stack([m["routed_pairs"], m["experts_hit"]])


class TrinityDecoderLayer(Module):
    """The sandwich block, under the names ``models/paged.py: _residual``
    reads (``input_layernorm_2`` after attention,
    ``post_attention_layernorm_2`` after the MLP)."""

    def __init__(self, cfg: TrinityConfig, layer_idx: int):
        super().__init__()
        norm = lambda: LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                    cfg.dtype)
        self.input_layernorm = norm()
        self.self_attn = TrinityAttention(cfg, cfg.layer_types[layer_idx])
        self.input_layernorm_2 = norm()
        self.post_attention_layernorm = norm()
        self.sparse = layer_idx >= cfg.num_dense_layers
        self.mlp = TrinityMoE(cfg) if self.sparse else LlamaMLP(cfg)
        self.post_attention_layernorm_2 = norm()

    def __call__(self, x):
        x = x + self.input_layernorm_2(
            self.self_attn(self.input_layernorm(x)))
        y = self.mlp(self.post_attention_layernorm(x))
        return x + self.post_attention_layernorm_2(y[0] if self.sparse else y)


class TrinityForCausalLM(Module):
    def __init__(self, cfg: TrinityConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = init((cfg.vocab_size, cfg.hidden_size),
                                 cfg.dtype)
        # muP: the embedding's rows times sqrt(hidden) (paged forwards too)
        self.embed_scale = (float(cfg.hidden_size) ** 0.5
                            if cfg.mup_enabled else None)
        self.layers = [TrinityDecoderLayer(cfg, i)
                       for i in range(cfg.num_hidden_layers)]
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                 cfg.dtype)
        self.lm_head = init((cfg.hidden_size, cfg.vocab_size), cfg.dtype)

    def logits(self, x):
        """The head, its logits float32 as they leave the accumulator: over
        200k vocabulary rows the best two logits of a position lie ~0.2
        apart, and a bfloat16 logit of magnitude 4-8 is a multiple of 0.03:
        rounded, one position in eight ties or swaps its best two."""
        if hasattr(self.lm_head, "dequantize"):
            return wo_matmul(x, self.lm_head)
        return jnp.dot(x, self.lm_head, preferred_element_type=jnp.float32)

    def __call__(self, input_ids):
        """Plain forward, no cache -> logits."""
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        if self.embed_scale:
            x = x * jnp.asarray(self.embed_scale, x.dtype)
        for lyr in self.layers:
            x = lyr(x)
        return self.logits(self.norm(x))
