"""Ouro looped language models (ByteDance ``Ouro-1.4B`` / ``Ouro-2.6B``,
``model_type: ouro``): a LLaMA-shaped decoder whose whole stack of layers
runs ``total_ut_steps`` times a token with the same weights.

What differs from :mod:`paddle_tpu.models.llama`:

* the **sandwich layer**: the attention and the MLP branch each pass a
  norm of their own (``input_layernorm_2``, ``post_attention_layernorm_2``)
  before the residual add;
* the **loop**: pass ``u`` runs layers ``0..L-1`` and ends in the final
  norm; its output is what pass ``u + 1`` starts from, and what the exit
  gate (hidden -> 1, with a bias) and the head read. Every (pass, layer)
  pair keeps K/V of its own: served, the paged cache holds ``L x U``
  cache layers (``models/paged.py``: ``cache_layers``, ``_run_stack``);
* the **exit rule**: ``lambda_u = sigmoid(gate_u)``, ``p_u = lambda_u *
  prod_{v<u}(1 - lambda_v)`` for ``u < U`` and ``p_U`` the remainder; a
  token leaves at the first pass whose cumulative ``p`` reaches
  ``early_exit_threshold``, else at ``U``. At the published threshold 1
  every token runs all the passes (taken as a static: a sigmoid that
  saturates in floating point lets no token out early).

The attention and MLP are LLaMA's own, under LLaMA's names, so the
weight-only quantisation and the paged forwards walk this model as they
walk LLaMA.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.module import Module
from paddle_tpu.models.llama import (LlamaAttention, LlamaConfig,
                                     LlamaForCausalLM, LlamaMLP, LlamaModel,
                                     LlamaRMSNorm)
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops import attention as A


@dataclass
class OuroConfig(LlamaConfig):
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0

    @staticmethod
    def tiny(**kw):
        return OuroConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=128,
            total_ut_steps=4, dtype=jnp.float32, remat=False), **kw})


class OuroDecoderLayer(Module):
    """The LLaMA layer's parts plus a norm of each branch before its add."""

    def __init__(self, cfg: OuroConfig):
        super().__init__()
        norm = lambda: LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                    cfg.dtype)
        self.input_layernorm = norm()
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = norm()
        self.mlp = LlamaMLP(cfg)
        self.input_layernorm_2 = norm()
        self.post_attention_layernorm_2 = norm()

    def __call__(self, x, cos, sin, attn_mask=None):
        h = self.input_layernorm(x)
        with jax.named_scope("attention"):
            x = residual(x, self.self_attn(h, cos, sin, attn_mask),
                         self.input_layernorm_2)
        h = self.post_attention_layernorm(x)
        with jax.named_scope("mlp"):
            x = residual(x, self.mlp(h), self.post_attention_layernorm_2)
        return x


def residual(x, branch, norm=None):
    """``x + branch``, the branch through its own norm where the layer has
    one (the sandwich layer). Without one this is the LLaMA add."""
    return x + (branch if norm is None else norm(branch))


def exit_pass(gates, threshold: float):
    """gates [U, ...] -> the 0-based pass at which each token leaves: the
    first ``u`` whose cumulative exit probability reaches ``threshold``,
    else the last."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)            # prod_{v<=u}(1 - lam_v)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = lam * before
    p = p.at[-1].set(before[-1])                     # p_U: what is left
    reached = jnp.cumsum(p, axis=0) >= threshold
    reached = reached.at[-1].set(True)
    return jnp.argmax(reached, axis=0)


class OuroModel(LlamaModel):
    def __init__(self, cfg: OuroConfig):
        if cfg.scan_layers:
            raise NotImplementedError(
                "OuroModel loops over the stack itself; scan_layers is not "
                "supported")
        Module.__init__(self)
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = init((cfg.vocab_size, cfg.hidden_size), cfg.dtype)
        self.set_pspec("embed_tokens", P("tp", None))
        self.layers = [OuroDecoderLayer(cfg)
                       for _ in range(cfg.num_hidden_layers)]
        self.layers_stacked = None
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        # the exit gate: hidden -> 1, with a bias
        self.early_exit_gate_w = init((cfg.hidden_size, 1), cfg.dtype)
        self.early_exit_gate_b = jnp.zeros((1,), cfg.dtype)

    def gate(self, x):
        return (x @ self.early_exit_gate_w + self.early_exit_gate_b)[..., 0]

    def __call__(self, input_ids, attn_mask=None, position_ids=None):
        """-> (the normed state of each token's exit pass [B, S, hidden],
        the gates of every pass [U, B, S])."""
        cfg = self.cfg
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        from paddle_tpu.distributed.sharded import maybe_shard
        x = maybe_shard(x, ("dp", "fsdp"), "sp", None)
        cos, sin = A.rope_cos_sin(
            input_ids.shape[1], cfg.hidden_size // cfg.num_attention_heads,
            base=cfg.rope_theta, position_ids=position_ids,
            scaling=cfg.rope_scaling,
            max_position_embeddings=cfg.max_position_embeddings)
        layer_fn = lambda lyr, h: lyr(h, cos, sin, attn_mask)
        if cfg.remat:
            names = cfg.save_names()
            layer_fn = jax.checkpoint(
                layer_fn, policy=(
                    jax.checkpoint_policies.save_only_these_names(*names)
                    if names else None))
        states, gates = [], []
        for _ in range(cfg.total_ut_steps):
            with jax.named_scope("ut_step"):
                for lyr in self.layers:
                    x = layer_fn(lyr, x)
                x = self.norm(x)             # what the next pass starts from
            states.append(x)
            gates.append(self.gate(x))
        gates = jnp.stack(gates)
        if cfg.early_exit_threshold >= 1:
            return x, gates
        at = exit_pass(gates, cfg.early_exit_threshold)
        return jnp.take_along_axis(jnp.stack(states), at[None, ..., None],
                                   axis=0)[0], gates


class OuroForCausalLM(LlamaForCausalLM):
    """``logits``, ``loss`` and the head are LLaMA's; ``__call__`` returns
    the logits of each token's exit pass."""

    backbone = OuroModel

    def __call__(self, input_ids, attn_mask=None, position_ids=None):
        hidden, _ = self.model(input_ids, attn_mask, position_ids)
        with jax.named_scope("lm_head"):
            return self.logits(hidden)

    def forward_with_gates(self, input_ids, attn_mask=None):
        """-> (logits, gates [U, B, S]): the published forward in full."""
        hidden, gates = self.model(input_ids, attn_mask)
        return self.logits(hidden), gates
