"""BERT (ref: PaddleNLP ``paddlenlp/transformers/bert/modeling.py`` and the
reference's Fleet data-parallel BERT pretraining config).

TPU-first: post-LN encoder stack with fused attention dispatch; MLM+NSP
pretraining heads; batch rides the (dp, fsdp) axes — pure data parallel is
just the mesh with tp=1.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layers import Dropout, Embedding, LayerNorm, Linear
from paddle_tpu.nn.transformer import MultiHeadAttention


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    dtype: object = jnp.float32
    # padded-varlen attention: interpret attention_mask as a CONTIGUOUS
    # prefix (standard right-padding) and pass per-row lengths to the fused
    # flash kernel instead of a dense additive mask (which forces the XLA
    # fallback). Ref: flash_attn varlen / PaddleNLP padded-batch pretraining.
    varlen_attention: bool = False

    @staticmethod
    def base(**kw):
        return BertConfig(**kw)

    @staticmethod
    def large(**kw):
        return BertConfig(**{**dict(hidden_size=1024, num_hidden_layers=24,
                                    num_attention_heads=16, intermediate_size=4096), **kw})

    @staticmethod
    def tiny(**kw):
        return BertConfig(**{**dict(vocab_size=128, hidden_size=32,
                                    num_hidden_layers=2, num_attention_heads=2,
                                    intermediate_size=64, max_position_embeddings=64,
                                    type_vocab_size=2), **kw})


class BertEmbeddings(Module):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.word_embeddings = Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_init=init, dtype=cfg.dtype)
        self.position_embeddings = Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                             weight_init=init, dtype=cfg.dtype)
        self.token_type_embeddings = Embedding(cfg.type_vocab_size, cfg.hidden_size,
                                               weight_init=init, dtype=cfg.dtype)
        self.layer_norm = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps, dtype=cfg.dtype)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def __call__(self, input_ids, token_type_ids=None, position_ids=None, rng=None):
        s = input_ids.shape[1]
        if position_ids is None:
            position_ids = jnp.arange(s)[None, :]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = (self.word_embeddings(input_ids)
             + self.position_embeddings(position_ids)
             + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(x), rng=rng)


class BertLayer(Module):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.attention = MultiHeadAttention(cfg.hidden_size, cfg.num_attention_heads,
                                            dropout=cfg.attention_probs_dropout_prob,
                                            dtype=cfg.dtype)
        self.attn_norm = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps, dtype=cfg.dtype)
        self.intermediate = Linear(cfg.hidden_size, cfg.intermediate_size, dtype=cfg.dtype)
        self.output = Linear(cfg.intermediate_size, cfg.hidden_size, dtype=cfg.dtype)
        self.out_norm = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps, dtype=cfg.dtype)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def __call__(self, x, attn_mask=None, rng=None, kv_lens=None):
        # three INDEPENDENT dropout draws: attention-internal, post-attn
        # residual, post-FF residual
        r1, r2, r3 = ((None,) * 3 if rng is None
                      else tuple(jax.random.split(rng, 3)))
        h = self.attention(x, attn_mask=attn_mask, rng=r1, kv_lens=kv_lens)
        x = self.attn_norm(x + self.dropout(h, rng=r2))
        h = self.output(F.gelu(self.intermediate(x)))
        return self.out_norm(x + self.dropout(h, rng=r3))


class BertModel(Module):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.layers = [BertLayer(cfg) for _ in range(cfg.num_hidden_layers)]
        self.pooler = Linear(cfg.hidden_size, cfg.hidden_size, dtype=cfg.dtype)

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 rng=None, position_ids=None):
        kv_lens = None
        if attention_mask is not None:
            if self.cfg.varlen_attention:
                # contiguous right-padding only: lengths keep the fused
                # kernel. Guard eagerly-passed masks (a traced mask inside
                # jit cannot be checked — the contract is documented).
                if not isinstance(attention_mask, jax.core.Tracer):
                    am = np.asarray(attention_mask)
                    lens_np = am.sum(axis=1)
                    prefix = (np.arange(am.shape[1])[None, :]
                              < lens_np[:, None]).astype(am.dtype)
                    if not np.array_equal(am, prefix):
                        raise ValueError(
                            "varlen_attention=True requires a CONTIGUOUS "
                            "right-padded attention_mask (1s then 0s); got "
                            "a non-prefix mask — use varlen_attention="
                            "False for arbitrary masks")
                kv_lens = jnp.sum(attention_mask.astype(jnp.int32), axis=1)
                attention_mask = None
            else:
                # [B, S] 1/0 -> additive mask [B, 1, 1, S]
                attention_mask = (1.0 - attention_mask[:, None, None, :]
                                  .astype(jnp.float32)) * -1e9
        x = self.embeddings(input_ids, token_type_ids,
                            position_ids=position_ids, rng=rng)
        for i, lyr in enumerate(self.layers):
            sub = None if rng is None else jax.random.fold_in(rng, i)
            x = lyr(x, attn_mask=attention_mask, rng=sub, kv_lens=kv_lens)
        pooled = jnp.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(Module):
    """MLM + NSP heads (ref BertForPretraining)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.mlm_transform = Linear(cfg.hidden_size, cfg.hidden_size, dtype=cfg.dtype)
        self.mlm_norm = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps, dtype=cfg.dtype)
        self.mlm_bias = jnp.zeros((cfg.vocab_size,), cfg.dtype)
        self.nsp_head = Linear(cfg.hidden_size, 2, dtype=cfg.dtype)

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None, rng=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask, rng=rng)
        h = self.mlm_norm(F.gelu(self.mlm_transform(seq)))
        mlm_logits = h @ self.bert.embeddings.word_embeddings.weight.T + self.mlm_bias
        nsp_logits = self.nsp_head(pooled)
        return mlm_logits, nsp_logits

    def loss(self, input_ids, mlm_labels, nsp_labels=None, token_type_ids=None,
             attention_mask=None, rng=None):
        mlm_logits, nsp_logits = self(input_ids, token_type_ids, attention_mask, rng=rng)
        mlm = F.cross_entropy(mlm_logits, jnp.maximum(mlm_labels, 0), reduction="none")
        mask = (mlm_labels >= 0).astype(jnp.float32)
        loss = jnp.sum(mlm * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        if nsp_labels is not None:
            loss = loss + F.cross_entropy(nsp_logits, nsp_labels)
        return loss


class BertForSequenceClassification(Module):
    def __init__(self, cfg: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(cfg)
        self.classifier = Linear(cfg.hidden_size, num_classes, dtype=cfg.dtype)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None, rng=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask, rng=rng)
        return self.classifier(self.dropout(pooled, rng=rng))
