"""Kimi-K2 (moonshotai ``Kimi-K2-Instruct``, ``model_type: kimi_k2``): the
DeepSeek-V3 block. Pre-norm, RMSNorm, no biases:

    x = x + MLA(norm1(x));  x = x + F(norm2(x))

``F`` is a SwiGLU MLP in the first ``first_k_dense_replace`` layers and the
expert layer in every later one: ``Shared(u) + sum_{e in top-k} g_e
Expert_e(u)`` with the sigmoid gate and its selection bias
(``distributed.moe.sigmoid_bias_gate``).

**Latent attention (MLA).** For a token with normed input ``u``:

    c_q = rmsnorm(u W_qa);  q = c_q W_qb        H heads of [q_nope | q_rope]
    [c_kv | k_r] = u W_kva; c_kv = rmsnorm(c_kv); k_r = rope(k_r)
    [k_nope_h | v_h] = c_kv W_kvb
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + rope(q_rope_h)(t).k_r(s)) * scale

``k_r`` is ONE rotated key for all heads; the cache row of a position in a
layer is ``[c_kv | k_r]`` (``kv_lora_rank + qk_rope_head_dim`` values),
written after the norm and the rotation. ``scale = (qk_nope + qk_rope)^-0.5
* m^2`` with YaRN's ``m`` (``ops.attention.yarn_mscale``); the rotation's
own cos and sin are not scaled (``mscale == mscale_all_dim``). Rope is YaRN
over the ``qk_rope_head_dim`` dims (``ops.attention.yarn_inv_freq``),
rotating halves of those dims as they lie (the published code first
de-interleaves them: a fixed permutation of columns, which seeded random
weights cannot tell apart).

Two forms of the same mathematics, chosen by the call site. The EXPANDED
one (per-head K of ``qk_nope + qk_rope`` and V of ``v_head_dim`` from the
latent; :meth:`KimiK2Attention.expanded`) is what scores many queries
against a key: :meth:`KimiK2Attention.__call__` over a whole sequence, and
the paged PREFILL forwards (``models/paged.py: _latent_residual``), whose
kernel expands each block of cache rows in VMEM. The ABSORBED one
(:meth:`KimiK2Attention.absorbed`: ``q~_h = [q_nope_h W^K_h^T | q_rope_h]``
scored against the cache rows themselves, ``o_h = (sum_s p c_kv(s))
W^V_h``: 64 query heads to one cache head, nothing expanded) is the paged
DECODE TICK's, one query a row: it spends 3.4 times the score FLOPs and no
expansion, which pays under ~170 queries a key.

**One chip's share.** ``held_experts`` names the routed experts (global
ids) this model holds in each expert layer; the router keeps its published
width and routes over all ``n_routed_experts``, the layer computes the
shared expert and its own experts' part (``MoELayer(held=...)``).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Module
from paddle_tpu.distributed.moe import MoELayer
from paddle_tpu.models.llama import LlamaConfig, LlamaMLP, LlamaRMSNorm
from paddle_tpu.models.paged import LATENT_LAYER
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops import attention as A
from paddle_tpu.ops.pallas.latent_attention import latent_row_width
from paddle_tpu.quantization import wo_matmul


def _yarn_default():
    return {"type": "yarn", "factor": 32, "beta_fast": 1, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class KimiK2Config(LlamaConfig):
    """The published keys under their own names."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
    rope_scaling: dict | None = field(default_factory=_yarn_default)
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.827
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    # the routed experts (global ids) held here; None: all of them
    held_experts: tuple | None = None
    remat: bool = False

    def __post_init__(self):
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)
        kind = (self.rope_scaling or {}).get("type")
        self._refuse(
            (self.n_group, self.topk_group) != (1, 1) and
            f"group-limited routing (n_group {self.n_group}, topk_group "
            f"{self.topk_group})",
            (self.scoring_func, self.topk_method) != ("sigmoid", "noaux_tc")
            and f"the {self.scoring_func!r} / {self.topk_method!r} gate",
            self.moe_layer_freq != 1 and
            f"moe_layer_freq {self.moe_layer_freq}",
            kind not in (None, "yarn") and f"rope_scaling type {kind!r}")

    @staticmethod
    def _refuse(*reasons):
        for why in reasons:
            if why:
                raise NotImplementedError(f"Kimi-K2 with {why} is not built")

    @property
    def layer_types(self) -> tuple:
        """Every layer keeps latent rows (``models.paged.layer_kinds``)."""
        return (LATENT_LAYER,) * self.num_hidden_layers

    @property
    def softmax_scale(self) -> float:
        m = A.yarn_mscale(self.rope_scaling) if self.rope_scaling else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @staticmethod
    def tiny(**kw):
        """The published ratios at toy widths: two rope dims to four nope,
        a latent four times a head, 16 experts of which 4 a token."""
        return KimiK2Config(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=4096, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=16, num_experts_per_tok=4,
            rope_scaling={**_yarn_default(),
                          "original_max_position_embeddings": 64},
            dtype=jnp.float32), **kw})


def rope_tables(rope_dim, theta, scaling, positions):
    """cos, sin ``[..., rope_dim / 2]`` float32 at ``positions``."""
    if scaling:
        inv = A.yarn_inv_freq(rope_dim, theta, scaling)
    else:
        inv = theta ** (-jnp.arange(0, rope_dim, 2, jnp.float32) / rope_dim)
    f = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(f), jnp.sin(f)


def _rotate(x, cos, sin):
    """Rotate halves of the last dim; cos/sin broadcast against it."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2].astype(jnp.float32), x[..., d2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class KimiK2Attention(Module):
    """Multi-head latent attention; the pieces both forms share are
    methods, so the paged body and the plain forward run one code."""

    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        e, h = cfg.hidden_size, cfg.num_attention_heads
        self.num_heads = h
        self.nope, self.rope_dim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.v_dim, self.rank = cfg.v_head_dim, cfg.kv_lora_rank
        self.scale = cfg.softmax_scale
        self.rope_theta = float(cfg.rope_theta)
        self.rope_scaling = dict(cfg.rope_scaling) if cfg.rope_scaling \
            else None
        self.row_width = latent_row_width(self.rank, self.rope_dim)
        init = I.Normal(0.0, cfg.initializer_range)
        norm = lambda n: LlamaRMSNorm(n, cfg.rms_norm_eps, cfg.dtype)
        self.q_a_proj = init((e, cfg.q_lora_rank), cfg.dtype)
        self.q_a_layernorm = norm(cfg.q_lora_rank)
        self.q_b_proj = init((cfg.q_lora_rank,
                              h * (self.nope + self.rope_dim)), cfg.dtype)
        # [c_kv | k_r]: the published ``kv_a_proj_with_mqa``
        self.kv_a_proj = init((e, self.rank + self.rope_dim), cfg.dtype)
        self.kv_a_layernorm = norm(self.rank)
        # a head's columns are [k_nope | v]
        self.kv_b_proj = init((self.rank, h * (self.nope + self.v_dim)),
                              cfg.dtype)
        self.o_proj = init((h * self.v_dim, e), cfg.dtype)

    def rope(self, positions):
        """cos, sin [B, S, rope / 2] at ``positions`` [B, S]."""
        return rope_tables(self.rope_dim, self.rope_theta, self.rope_scaling,
                           positions)

    def queries(self, u, cos, sin):
        """-> q_nope [B, S, H, nope], rotated q_rope [B, S, H, rope]."""
        b, s, _ = u.shape
        q = wo_matmul(self.q_a_layernorm(wo_matmul(u, self.q_a_proj)),
                      self.q_b_proj)
        q = q.reshape(b, s, self.num_heads, self.nope + self.rope_dim)
        return (q[..., :self.nope],
                _rotate(q[..., self.nope:], cos[:, :, None], sin[:, :, None]))

    def latent(self, u, cos, sin):
        """-> the normed latent c_kv [B, S, rank] and the one rotated key
        k_r [B, S, rope]: what a position's cache row holds."""
        kv = wo_matmul(u, self.kv_a_proj)
        return (self.kv_a_layernorm(kv[..., :self.rank]),
                _rotate(kv[..., self.rank:], cos, sin))

    def kv_b(self):
        """W_kvb as [rank, H, nope + v]: a head's columns ``[k_nope | v]``."""
        w = self.kv_b_proj
        if hasattr(w, "dequantize"):
            w = w.dequantize(self.o_proj.dtype)
        return w.reshape(self.rank, self.num_heads, self.nope + self.v_dim)

    def cache_rows(self, u, cos, sin):
        """[c_kv | k_r | 0] of ``row_width`` values, [B, S, W]."""
        c_kv, k_r = self.latent(u, cos, sin)
        pad = self.row_width - self.rank - self.rope_dim
        return jnp.pad(jnp.concatenate([c_kv, k_r], axis=-1),
                       ((0, 0), (0, 0), (0, pad)))

    def project(self, o):
        """o [B, S, H, v] -> the branch's output [B, S, hidden]."""
        b, s = o.shape[:2]
        return wo_matmul(o.reshape(b, s, self.num_heads * self.v_dim),
                         self.o_proj)

    def absorbed(self, u, cos, sin, attend):
        """The absorbed form: ``attend(q~ [B, S, H, W]) -> [B, S, H, rank]``
        (probabilities times latents) is the caller's attention of
        ``q~ = [q_nope W^K^T | q_rope | 0]`` over the cache rows themselves;
        W^V a head, then ``o_proj``."""
        q_nope, q_rope = self.queries(u, cos, sin)
        w_k = self.kv_b()[..., :self.nope]                # [rank, H, nope]
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_k.astype(u.dtype))
        pad = self.row_width - self.rank - self.rope_dim
        o_lat = attend(jnp.pad(jnp.concatenate([q_lat, q_rope], axis=-1),
                               ((0, 0), (0, 0), (0, 0), (0, pad))))
        w_v = self.kv_b()[..., self.nope:]                # [rank, H, v]
        return self.project(jnp.einsum("bshc,chd->bshd", o_lat,
                                       w_v.astype(o_lat.dtype)))

    def expanded(self, u, cos, sin, attend):
        """The expanded form: ``attend(q_nope, q_rope, W_kvb) -> [B, S, H,
        v]`` is the caller's attention of the per-head queries over the K
        and V it expands from the latents; then ``o_proj``."""
        return self.project(attend(*self.queries(u, cos, sin), self.kv_b()))

    def __call__(self, u, cos, sin):
        """The expanded form, causal over the whole of ``u`` [B, S, E]."""
        s = u.shape[1]
        q_nope, q_rope = self.queries(u, cos, sin)
        c_kv, k_r = self.latent(u, cos, sin)
        kv = jnp.einsum("bsc,chd->bshd", c_kv,
                        self.kv_b().astype(c_kv.dtype))
        k_nope, v = kv[..., :self.nope], kv[..., self.nope:]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r,
                               preferred_element_type=jnp.float32))
        keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(keep, scores * self.scale, -1e30), -1)
        return self.project(jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype),
                                       v))


class KimiK2MoE(Module):
    """The expert layer: the shared expert for every token plus the routed
    sum over the experts held here. -> (y, counts): ``counts`` int32 [2],
    the (token, expert) pairs routed to a held expert and the held experts
    that got at least one (``routed_pairs``, ``experts_hit`` on the
    serving spans)."""

    counts_routed = True      # ``models.paged.counts_routed``

    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.moe = MoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
            k=cfg.num_experts_per_tok, capacity_factor=None, dtype=cfg.dtype,
            norm_topk_prob=cfg.norm_topk_prob, router="sigmoid_bias",
            routed_scale=cfg.routed_scaling_factor, held=cfg.held_experts)
        self.shared = None
        if cfg.n_shared_experts:
            self.shared = LlamaMLP(replace(
                cfg, intermediate_size=(cfg.n_shared_experts
                                        * cfg.moe_intermediate_size)))

    def __call__(self, x, live=None):
        """``live`` [B, S] bool: False a padding token, routed nowhere."""
        y, _, m = self.moe(x, return_metrics=True, live=live)
        if self.shared is not None:
            y = y + self.shared(x)
        return y, jnp.stack([m["routed_pairs"], m["experts_hit"]])


class KimiK2DecoderLayer(Module):
    def __init__(self, cfg: KimiK2Config, layer_idx: int):
        super().__init__()
        norm = lambda: LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                    cfg.dtype)
        self.input_layernorm = norm()
        self.self_attn = KimiK2Attention(cfg)
        self.post_attention_layernorm = norm()
        self.sparse = layer_idx >= cfg.first_k_dense_replace
        self.mlp = KimiK2MoE(cfg) if self.sparse else LlamaMLP(cfg)

    def __call__(self, x, cos, sin):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin)
        y = self.mlp(self.post_attention_layernorm(x))
        return x + (y[0] if self.sparse else y)


class KimiK2ForCausalLM(Module):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = init((cfg.vocab_size, cfg.hidden_size),
                                 cfg.dtype)
        self.layers = [KimiK2DecoderLayer(cfg, i)
                       for i in range(cfg.num_hidden_layers)]
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                 cfg.dtype)
        self.lm_head = init((cfg.hidden_size, cfg.vocab_size), cfg.dtype)

    def __call__(self, input_ids):
        """Plain forward, the expanded attention, no cache -> logits."""
        s = input_ids.shape[1]
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        for lyr in self.layers:
            x = lyr(x, *lyr.self_attn.rope(jnp.arange(s)[None, :]))
        return wo_matmul(self.norm(x), self.lm_head)
