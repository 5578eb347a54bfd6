"""LLaMA-2 family (flagship; ref: PaddleNLP ``paddlenlp/transformers/llama/
modeling.py`` + ``llm/llama`` training entrypoints).

TPU-first design decisions vs the reference:
  * bf16 params by default with fp32 master weights in the optimizer.
  * fused QKV and gate+up projections — two big MXU matmuls instead of five.
  * attention through the Pallas flash kernel ([B,S,H,D] layout).
  * tensor parallel via PartitionSpecs (qkv/gate_up column-, o/down row-
    sharded on ``tp``); sequence axis optionally sharded on ``sp``.
  * per-layer ``jax.checkpoint`` (remat) instead of the reference's
    recompute pass.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layers import Dropout, Embedding, Linear
from paddle_tpu.ops import attention as A
from paddle_tpu.ops import fused_rms_norm


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: object = jnp.bfloat16
    remat: bool = True
    # selective remat (VERDICT r4 item 1): what the per-layer checkpoint
    # SAVES instead of recomputing in backward. The tags live on the
    # layer's named activations (checkpoint_name below); recompute cost
    # falls as more is saved, HBM cost rises:
    #   None/"full"  save nothing (classic full remat — max recompute)
    #   "hidden"     save the hidden-sized dot outputs (attn context,
    #                attn out, ffn down out) — recomputes qkv + gate/up
    #   "no_ffn"     save every named activation EXCEPT the [B,S,2m]
    #                gate/up intermediate (the one that doesn't fit) —
    #                backward recomputes only gate/up + elementwise
    #   "dots"       save all dot outputs (near no-remat recompute, most
    #                memory that still skips attention internals)
    remat_policy: str | None = None
    use_flash: bool = True
    fp8: bool = False  # e4m3/e5m2 projections with delayed scaling (amp.fp8)
    scan_layers: bool = False  # stack layers + lax.scan: O(1) compile depth
    sliding_window: int | None = None  # Mistral-style causal window
    attention_bias: bool = False       # Qwen2: bias on fused qkv only
    sequence_parallel: str | None = None  # "ring" | "ulysses" over sp
    # long-context extension (ref rope_scaling: linear | ntk | dynamic)
    rope_scaling: dict | None = None

    def save_names(self) -> tuple:
        """The checkpoint_name tags each remat_policy mode SAVES (see the
        field comment above); everything else is recomputed in backward."""
        try:
            return _REMAT_SAVE_NAMES[self.remat_policy]
        except KeyError:
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; expected one "
                f"of {sorted(k for k in _REMAT_SAVE_NAMES if k)} or None")

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**{**dict(hidden_size=4096, intermediate_size=11008,
                                     num_hidden_layers=32, num_attention_heads=32), **kw})

    @staticmethod
    def llama2_13b(**kw):
        return LlamaConfig(**{**dict(hidden_size=5120, intermediate_size=13824,
                                     num_hidden_layers=40, num_attention_heads=40,
                                     num_key_value_heads=40), **kw})

    @staticmethod
    def tiny(**kw):
        return LlamaConfig(**{**dict(vocab_size=256, hidden_size=64,
                                     intermediate_size=128, num_hidden_layers=2,
                                     num_attention_heads=4, num_key_value_heads=2,
                                     max_position_embeddings=128,
                                     dtype=jnp.float32, remat=False), **kw})


# remat_policy mode -> checkpoint_name tags saved by the per-layer
# jax.checkpoint (empty = classic full remat: save nothing named)
_REMAT_SAVE_NAMES = {
    None: (), "full": (),
    "hidden": ("attn_ctx", "ffn_out"),
    "no_ffn": ("qkv", "attn_ctx", "ffn_out"),
    "dots": ("qkv", "attn_ctx", "ffn_gu", "ffn_out"),
}


class LlamaRMSNorm(Module):
    def __init__(self, size, eps, dtype):
        super().__init__()
        self.weight = jnp.ones((size,), dtype)
        self.eps = eps

    def __call__(self, x):
        with jax.named_scope("norm"):
            return fused_rms_norm(x, self.weight, self.eps)


class LlamaAttention(Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, nh, nkv = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads
        self.head_dim = h // nh
        init = I.Normal(0.0, cfg.initializer_range)
        # fused qkv: [h, (nh + 2*nkv) * head_dim], column-parallel on tp
        self.qkv_proj = init((h, (nh + 2 * nkv) * self.head_dim), cfg.dtype)
        self.o_proj = init((nh * self.head_dim, h), cfg.dtype)
        self.set_pspec("qkv_proj", P(None, "tp"))
        self.set_pspec("o_proj", P("tp", None))
        if cfg.attention_bias:  # Qwen2: q/k/v biased, o_proj not
            self.qkv_bias = jnp.zeros(((nh + 2 * nkv) * self.head_dim,), cfg.dtype)
            self.set_pspec("qkv_bias", P("tp"))
        else:
            self.qkv_bias = None
        self.num_heads, self.num_kv_heads = nh, nkv
        self.use_flash = cfg.use_flash
        self.window = cfg.sliding_window
        self.sequence_parallel = cfg.sequence_parallel
        if cfg.fp8:
            from paddle_tpu.amp.fp8 import new_fp8_meta
            self.fp8_meta = {"qkv": new_fp8_meta(), "o": new_fp8_meta()}
        else:
            self.fp8_meta = None

    def _attend(self, q, k, v, attn_mask):
        # sequence parallelism over the sp axis — trace-time dispatch,
        # falling back to flash/XLA attention when no sp mesh is active:
        #   "ring":    KV blocks rotate on ICI (ppermute) while the MXU
        #              works on the current block; best when S/chip is big.
        #   "ulysses": two all_to_alls re-shard seq<->heads and full
        #              attention (incl. the flash kernel) runs on a head
        #              slice; best when num_heads >= sp and S/chip is small.
        if self.sequence_parallel in ("ring", "ulysses"):
            from paddle_tpu.distributed.mesh import current_mesh
            mesh = current_mesh()
            if mesh is not None and mesh.size("sp") > 1:
                # normalise attn_mask into one of the two sp-path forms:
                #   mask3: [B, S, S] bool over global positions (boolean
                #     masks; [B, S] / [B,1,1,S] key padding broadcasts)
                #   bias4: [B|1, H|1, S, S] float ADDITIVE scores — soft
                #     biases (ALiBi/T5 relative bias) AND per-head bool
                #     masks (folded to 0/-inf), which have no [B,S,S] form
                mask3 = None
                bias4 = None
                s_full = q.shape[1]
                if attn_mask is not None:
                    m = attn_mask
                    per_head = m.ndim == 4 and m.shape[1] > 1
                    if jnp.issubdtype(m.dtype, jnp.floating) or per_head:
                        if m.dtype == jnp.bool_:
                            m = jnp.where(m, 0.0, -1e30)
                        m = m.astype(jnp.float32)
                        if m.ndim == 2:
                            m = m[None, None]      # [S,S] or [1,S] rows
                        elif m.ndim == 3:
                            m = m[:, None]         # [B,S,S] -> [B,1,S,S]
                        if m.shape[2] == 1:        # broadcast rows to S
                            m = jnp.broadcast_to(
                                m, m.shape[:2] + (s_full, m.shape[3]))
                        bias4 = m
                    else:
                        m = m.astype(bool)
                        if m.ndim == 4:
                            m = m[:, 0]          # [B,(1|S),S]
                        elif m.ndim == 2:
                            m = m[:, None, :]    # key padding -> rows
                        if m.shape[1] == 1:
                            m = jnp.broadcast_to(
                                m, (m.shape[0], s_full, s_full))
                        mask3 = m
                from paddle_tpu.distributed.sp import sp_attention
                head_spec = "tp" if mesh.size("tp") > 1 else None
                return sp_attention(mesh, self.sequence_parallel, q, k, v,
                                    causal=True, window=self.window,
                                    head_spec=head_spec, attn_mask=mask3,
                                    attn_bias=bias4)
        return F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=True,
            training=self.training, window=self.window)

    def __call__(self, x, cos, sin, attn_mask=None):
        b, s, h = x.shape
        nh, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        if self.fp8_meta is not None:
            from paddle_tpu.amp.fp8 import fp8_matmul
            qkv = fp8_matmul(x, self.qkv_proj, self.fp8_meta["qkv"])
        else:
            from paddle_tpu.quantization import wo_matmul
            qkv = wo_matmul(x, self.qkv_proj)
        if self.qkv_bias is not None:
            qkv = qkv + self.qkv_bias
        qkv = checkpoint_name(qkv, "qkv")
        q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
        q = q.reshape(b, s, nh, d)
        k = k.reshape(b, s, nkv, d)
        v = v.reshape(b, s, nkv, d)
        q = A.apply_rope(q, cos, sin)
        k = A.apply_rope(k, cos, sin)
        out = self._attend(q, k, v, attn_mask)
        out = checkpoint_name(out, "attn_ctx")
        out = out.reshape(b, s, nh * d)
        if self.fp8_meta is not None:
            from paddle_tpu.amp.fp8 import fp8_matmul
            return fp8_matmul(out, self.o_proj, self.fp8_meta["o"])
        from paddle_tpu.quantization import wo_matmul
        return wo_matmul(out, self.o_proj)


class LlamaMLP(Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, m = cfg.hidden_size, cfg.intermediate_size
        init = I.Normal(0.0, cfg.initializer_range)
        # fused gate+up (SwiGLU): one [h, 2m] matmul
        self.gate_up_proj = init((h, 2 * m), cfg.dtype)
        self.down_proj = init((m, h), cfg.dtype)
        self.set_pspec("gate_up_proj", P(None, "tp"))
        self.set_pspec("down_proj", P("tp", None))
        self.intermediate_size = m
        if cfg.fp8:
            from paddle_tpu.amp.fp8 import new_fp8_meta
            self.fp8_meta = {"gate_up": new_fp8_meta(),
                             "down": new_fp8_meta()}
        else:
            self.fp8_meta = None

    def __call__(self, x):
        if self.fp8_meta is not None:
            from paddle_tpu.amp.fp8 import fp8_matmul
            gu = fp8_matmul(x, self.gate_up_proj, self.fp8_meta["gate_up"])
            gate, up = jnp.split(gu, 2, axis=-1)
            return fp8_matmul(jax.nn.silu(gate) * up, self.down_proj,
                              self.fp8_meta["down"])
        from paddle_tpu.quantization import wo_matmul
        gu = checkpoint_name(wo_matmul(x, self.gate_up_proj), "ffn_gu")
        gate, up = jnp.split(gu, 2, axis=-1)
        return checkpoint_name(
            wo_matmul(jax.nn.silu(gate) * up, self.down_proj), "ffn_out")


class LlamaDecoderLayer(Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        self.mlp = LlamaMLP(cfg)

    def __call__(self, x, cos, sin, attn_mask=None):
        # scopes are metadata: they split %fusion by part in a profile
        h = self.input_layernorm(x)
        with jax.named_scope("attention"):
            x = x + self.self_attn(h, cos, sin, attn_mask)
        h = self.post_attention_layernorm(x)
        with jax.named_scope("mlp"):
            x = x + self.mlp(h)
        return x


class LlamaModel(Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = init((cfg.vocab_size, cfg.hidden_size), cfg.dtype)
        self.set_pspec("embed_tokens", P("tp", None))
        layers = [LlamaDecoderLayer(cfg) for _ in range(cfg.num_hidden_layers)]
        if cfg.scan_layers:
            # stacked pytree [L, ...]: one traced layer, lax.scan over depth —
            # compile time independent of depth, leading axis a natural fsdp dim
            self.layers_stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=0), *layers)
            self.layers = []
        else:
            self.layers = layers
            self.layers_stacked = None
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)

    def __call__(self, input_ids, attn_mask=None, position_ids=None):
        cfg = self.cfg
        x = jnp.take(self.embed_tokens, input_ids, axis=0)
        # activations sharded batch over data axes, sequence over sp
        from paddle_tpu.distributed.sharded import maybe_shard
        x = maybe_shard(x, ("dp", "fsdp"), "sp", None)
        cos, sin = A.rope_cos_sin(input_ids.shape[1], cfg.hidden_size // cfg.num_attention_heads,
                                  base=cfg.rope_theta, position_ids=position_ids,
                                  scaling=cfg.rope_scaling,
                                  max_position_embeddings=cfg.max_position_embeddings)
        if cfg.remat:
            # selective remat: save only the tagged activations the policy
            # names (checkpoint_name tags in attention/MLP); None/"full"
            # saves nothing — classic full remat
            names = cfg.save_names()
            policy = (jax.checkpoint_policies.save_only_these_names(*names)
                      if names else None)
            layer_fn = jax.checkpoint(
                lambda lyr, h: lyr(h, cos, sin, attn_mask),
                static_argnums=(), policy=policy)
        else:
            layer_fn = (lambda lyr, h: lyr(h, cos, sin, attn_mask))
        if cfg.scan_layers:
            def body(h, lyr):
                return layer_fn(lyr, h), None
            x, _ = jax.lax.scan(body, x, self.layers_stacked)
        else:
            for lyr in self.layers:
                x = layer_fn(lyr, x)
        return self.norm(x)


class LlamaForCausalLM(Module):
    """Decoder LM with parallel (tp-sharded) LM head + fused CE."""

    backbone = LlamaModel        # a family with another stack names its own

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = self.backbone(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = I.Normal(0.0, cfg.initializer_range)(
                (cfg.hidden_size, cfg.vocab_size), cfg.dtype)
            self.set_pspec("lm_head", P(None, "tp"))

    def logits(self, hidden):
        from paddle_tpu.quantization import wo_matmul
        w = self.model.embed_tokens.T if self.lm_head is None else self.lm_head
        return wo_matmul(hidden, w)

    def __call__(self, input_ids, attn_mask=None, position_ids=None):
        hidden = self.model(input_ids, attn_mask, position_ids)
        with jax.named_scope("lm_head"):
            return self.logits(hidden)

    def loss(self, input_ids, labels, attn_mask=None):
        """Causal LM loss; labels = input shifted, ignore_index=-100."""
        from paddle_tpu.distributed.tensor_parallel import parallel_cross_entropy
        logits = self(input_ids, attn_mask)
        per_tok = parallel_cross_entropy(logits, jnp.maximum(labels, 0))
        mask = (labels >= 0).astype(jnp.float32)
        return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def llama_pipeline_train_step(model: "LlamaForCausalLM", mesh, input_ids,
                              labels, num_microbatches: int, batch_axes=(),
                              schedule: str = "1f1b"):
    """1F1B pipeline-parallel loss + grads for LLaMA over the pp mesh axis.

    Decoder layers are the pipeline stages; the embedding runs at stage 0
    and the (final-norm + lm_head + masked-CE) head at the last stage, both
    with replicated grads. Per-microbatch losses are averaged, which equals
    ``model.loss`` exactly when every microbatch masks the same number of
    label positions (the standard shifted-labels -100 tail does).
    Ref: ``python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py``.

    Returns ``(loss, grads)`` with ``grads = {layers, embed_tokens,
    norm_weight, lm_head}`` — ``layers`` stacked [L, ...] and sharded
    P("pp", ...) like the stage params. NOTE on a tp>1 mesh the layer
    grads come back in the tp-INTERLEAVED column layout (matching the
    weights the schedule trained on); convert to the canonical layout with
    ``tp_shuffle_llama_params(grads, cfg, tp, inverse=True)``.
    """
    _check_pp_model(model)
    params = _pp_params(model, copy=False)
    if hasattr(mesh, "size") and mesh.size("tp") > 1:
        params = tp_shuffle_llama_params(params, model.cfg, mesh.size("tp"))
    return _pp_loss_and_grads(model.cfg, len(model.model.layers), mesh,
                              params, input_ids, labels, num_microbatches,
                              batch_axes, schedule=schedule)


def _check_pp_model(model):
    assert model.lm_head is not None, \
        "pipeline head needs untied embeddings (tie_word_embeddings=False)"
    assert model.model.layers, "pipeline stages need scan_layers=False"


def _pp_params(model, copy: bool):
    """The canonical pp param tree. ``copy=True`` makes every leaf a fresh
    buffer so a DONATING train loop can never delete the module's own
    weights out from under later eval/checkpoint use."""
    from paddle_tpu.distributed.pipeline import stack_layers
    params = dict(layers=stack_layers(model.model.layers),  # stack = copy
                  embed_tokens=model.model.embed_tokens,
                  norm_weight=model.model.norm.weight,
                  lm_head=model.lm_head)
    if copy:
        params = {k: jax.tree_util.tree_map(jnp.copy, v) if k != "layers"
                  else v for k, v in params.items()}
    return PpParams.make(params, 1)


def make_llama_pp_train_step(model: "LlamaForCausalLM", mesh, optimizer,
                             num_microbatches: int, batch_axes=()):
    """End-to-end 1F1B TRAINING: a jitted ``step(params, opt_state, ids,
    labels) -> (params, opt_state, loss)`` where params =
    ``{layers (stacked, P("pp",...)), embed_tokens, norm_weight, lm_head}``
    and the optimizer consumes the pipeline's grads directly. Composes pp
    with dp via ``batch_axes`` (each dp member pipelines its batch shard;
    grads are dp-averaged inside the schedule). params and opt_state are
    DONATED each step (the reference make_train_step's memory discipline).

    Use ``init_llama_pp_state(model, optimizer)`` for the initial
    (params, opt_state).
    """
    _check_pp_model(model)
    # capture only scalars — holding the module would pin a duplicate set
    # of unstacked weights for the loop's lifetime
    cfg, n_layers = model.cfg, len(model.model.layers)

    def step(params, opt_state, input_ids, labels):
        loss, grads = _pp_loss_and_grads(
            cfg, n_layers, mesh, params, input_ids, labels,
            num_microbatches, batch_axes)
        new_params, new_opt = optimizer.step(params, grads, opt_state)
        return new_params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))


def _pp_loss_and_grads(cfg, n_layers, mesh, params, input_ids, labels,
                       num_microbatches, batch_axes, schedule="1f1b"):
    """The ONE pipeline-LLaMA forward/backward: reads weights from
    ``params`` ({layers, embed_tokens, norm_weight, lm_head}) so both the
    module-level wrapper (llama_pipeline_train_step) and the jitted
    optimizer loop share it."""
    from paddle_tpu.distributed.pipeline import (PipelineLayer,
                                                 pipeline_train_step)
    pipe = PipelineLayer.from_stacked(
        params["layers"], n_layers=n_layers, num_stages=mesh.pp,
        num_microbatches=num_microbatches, remat=cfg.remat)

    cos, sin = A.rope_cos_sin(input_ids.shape[1],
                              cfg.hidden_size // cfg.num_attention_heads,
                              base=cfg.rope_theta, scaling=cfg.rope_scaling,
                              max_position_embeddings=cfg.max_position_embeddings)
    eps = cfg.rms_norm_eps

    tp = mesh.size("tp") if hasattr(mesh, "size") else 1
    stage_specs = None
    if cfg.fp8:
        # fp8 amax-history leaves would travel through the schedule's
        # masked-sum dstage accumulator and come out scaled by 1/M (and
        # dp-meaned) — the optimizer's overwrite-with-gradient splice would
        # then install a mean of rolled histories instead of the step amax,
        # under-estimating amax and over-scaling into e4m3 clipping
        raise NotImplementedError(
            "fp8 delayed scaling is not supported inside the 1F1B pipeline "
            "(amax histories need max/last-write combining across "
            "microbatches, not the schedule's mean); train fp8 with GSPMD "
            "dp/tp/fsdp instead")
    if tp > 1:
        # manual tensor parallelism inside the pipeline: weights must be in
        # the tp-interleaved layout (tp_shuffle_llama_params) so each shard
        # holds matched q/k/v (gate/up) slices
        assert (cfg.num_attention_heads % tp == 0
                and cfg.num_key_value_heads % tp == 0
                and cfg.intermediate_size % tp == 0), \
            f"tp={tp} must divide heads/kv-heads/intermediate"
        layout = getattr(params, "tp_layout", None)
        if layout != tp:
            raise ValueError(
                f"params are in tp_layout={layout!r} but the mesh has "
                f"tp={tp}; build them with init_llama_pp_state(model, opt, "
                "mesh) / tp_shuffle_llama_params so the fused projections "
                "are interleaved for this tp degree (wrong-layout weights "
                "would silently split the wrong q/k/v columns)")
        from paddle_tpu.quantization import QuantizedWeight
        if any(isinstance(l, QuantizedWeight)
               for l in jax.tree_util.tree_leaves(
                   params["layers"], is_leaf=lambda x: isinstance(
                       x, QuantizedWeight))):
            raise NotImplementedError(
                "weight-only quantized layers are inference-path only; the "
                "manual-tp pipeline trains full-precision weights")
        layer_call = make_tp_layer_call(cos, sin)
        stage_specs = llama_tp_stage_specs(params["layers"])
    else:
        layout = getattr(params, "tp_layout", 1)
        if layout not in (None, 1):
            raise ValueError(
                f"params are tp-interleaved for tp={layout} but the mesh "
                "has tp=1; convert back with tp_shuffle_llama_params(..., "
                "inverse=True) first (the plain layer path would split the "
                "wrong q/k/v columns)")

        def layer_call(lyr, h):
            return lyr(h, cos, sin, None)

    def embed_fn(emb_w, ids):
        return jnp.take(emb_w, ids, axis=0)

    def head_loss(hp, hidden, lbl):
        norm_w, head_w = hp
        h = fused_rms_norm(hidden, norm_w, eps)
        logits = (h @ head_w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        per_tok = -jnp.take_along_axis(
            logp, jnp.maximum(lbl, 0)[..., None], -1)[..., 0]
        mask = (lbl >= 0).astype(jnp.float32)
        return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    loss, dstage, dembed, dhead = pipeline_train_step(
        pipe, mesh, input_ids, labels, layer_call=layer_call,
        head_loss_fn=head_loss,
        head_params=(params["norm_weight"], params["lm_head"]),
        embed_fn=embed_fn, embed_params=params["embed_tokens"],
        batch_axes=batch_axes, stage_specs=stage_specs, schedule=schedule)
    grads = PpParams.make(
        dict(layers=dstage, embed_tokens=dembed,
             norm_weight=dhead[0], lm_head=dhead[1]),
        getattr(params, "tp_layout", 1))
    return loss, grads


class PpParams(dict):
    """The canonical pp param tree with a STATIC layout tag: ``tp_layout``
    records which tp degree the fused projections are interleaved for
    (1 = canonical [Q|K|V]/[gate|up] order). The tag rides the pytree aux
    data, so it survives jit/donation/optimizer tree_maps — and the tp
    pipeline path can refuse weights in the wrong layout instead of
    silently splitting wrong columns."""

    tp_layout: int = 1

    @staticmethod
    def make(d: dict, tp_layout: int = 1) -> "PpParams":
        p = PpParams(d)
        p.tp_layout = tp_layout
        return p


jax.tree_util.register_pytree_with_keys(
    PpParams,
    lambda p: ([(jax.tree_util.DictKey(k), p[k]) for k in sorted(p)],
               (tuple(sorted(p)), p.tp_layout)),
    lambda aux, vals: PpParams.make(dict(zip(aux[0], vals)), aux[1]),
)


def _tp_interleave_perm(n_blocks_per_group: list[int], block: int, tp: int):
    """Column permutation turning globally-grouped fused projections (e.g.
    [Q|K|V] or [gate|up]) into per-tp-shard groups ([q0|k0|v0 | q1|k1|v1]).

    Contiguous tp column-sharding of a fused projection would otherwise
    hand shard 0 only Q (or only gate) columns — the standard Megatron
    trick is to pre-permute so every shard holds matched slices.
    ``n_blocks_per_group``: #blocks (of ``block`` columns) per fused group;
    each group's blocks are dealt round-robin-contiguously to shards."""
    import numpy as np
    offs = np.cumsum([0] + [n * block for n in n_blocks_per_group])
    perm = []
    for i in range(tp):
        for g, n in enumerate(n_blocks_per_group):
            per = n // tp
            start = offs[g] + i * per * block
            perm.extend(range(start, start + per * block))
    return np.asarray(perm)


def tp_shuffle_llama_params(params: dict, cfg: LlamaConfig, tp: int,
                            inverse: bool = False):
    """(Un)permute the stacked layer params for manual-tp pipeline use:
    qkv_proj / qkv_bias columns to per-shard [q_i|k_i|v_i], gate_up_proj
    columns to per-shard [g_i|u_i]. o_proj/down_proj need no permutation
    (their row order already matches the per-shard slices)."""
    import numpy as np
    cur = getattr(params, "tp_layout", 1) or 1
    want_cur = tp if inverse else 1
    if cur != want_cur:
        raise ValueError(
            f"tp_shuffle_llama_params: params are in tp_layout={cur}, "
            f"expected {want_cur} for {'inverse ' if inverse else ''}"
            f"shuffle to tp={tp} — double-(un)shuffling would scramble "
            "the fused projection columns")
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.hidden_size // cfg.num_attention_heads)
    m = cfg.intermediate_size
    qkv_perm = _tp_interleave_perm([nh, nkv, nkv], hd, tp)
    gu_perm = _tp_interleave_perm([m, m], 1, tp)
    if inverse:
        qkv_perm = np.argsort(qkv_perm)
        gu_perm = np.argsort(gu_perm)
    layers = params["layers"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(layers)
    out = []
    for path, leaf in flat:
        from paddle_tpu.core.module import _path_to_str
        ps = _path_to_str(path)
        if leaf is None:
            out.append(leaf)
        elif ps.endswith("qkv_proj") or ps.endswith("qkv_bias"):
            out.append(leaf[..., qkv_perm])
        elif ps.endswith("gate_up_proj"):
            out.append(leaf[..., gu_perm])
        else:
            out.append(leaf)
    new = {**params, "layers": jax.tree_util.tree_unflatten(treedef, out)}
    return PpParams.make(new, 1 if inverse else tp)


def make_tp_layer_call(cos, sin, tp_axis: str = "tp"):
    """Decoder-layer call for MANUAL tensor parallelism inside shard_map:
    local q/k/v head slices attend locally; the row-parallel o_proj and
    down_proj partial products are psum'd over the tp axis. Expects weights
    permuted by ``tp_shuffle_llama_params``."""
    from jax import lax as _lax

    def call(lyr, h):
        att, mlp = lyr.self_attn, lyr.mlp
        tp = _lax.axis_size(tp_axis)
        hd = att.head_dim
        nh_l = att.num_heads // tp
        nkv_l = att.num_kv_heads // tp

        x = h
        hn = lyr.input_layernorm(x)
        qkv = hn @ att.qkv_proj                      # local columns
        if att.qkv_bias is not None:
            qkv = qkv + att.qkv_bias
        b, s, _ = hn.shape
        q, k, v = jnp.split(qkv, [nh_l * hd, (nh_l + nkv_l) * hd], axis=-1)
        q = A.apply_rope(q.reshape(b, s, nh_l, hd), cos, sin)
        k = A.apply_rope(k.reshape(b, s, nkv_l, hd), cos, sin)
        v = v.reshape(b, s, nkv_l, hd)
        ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             window=att.window)
        partial_o = ctx.reshape(b, s, nh_l * hd) @ att.o_proj
        x = x + _lax.psum(partial_o, tp_axis)        # row-parallel reduce

        hn2 = lyr.post_attention_layernorm(x)
        gu = hn2 @ mlp.gate_up_proj                  # local [g_i|u_i]
        gate, up = jnp.split(gu, 2, axis=-1)
        partial_d = (jax.nn.silu(gate) * up) @ mlp.down_proj
        return x + _lax.psum(partial_d, tp_axis)
    return call


def llama_tp_stage_specs(stacked, tp_axis: str = "tp"):
    """Per-leaf specs for the STACKED [L, ...] layer tree:
    P("pp", *tp_spec) — fused projections column-sharded, o/down
    row-sharded over tp, everything else replicated over tp."""
    from paddle_tpu.core.module import _path_to_str
    flat, treedef = jax.tree_util.tree_flatten_with_path(stacked)
    specs = []
    for path, leaf in flat:
        if leaf is None or not hasattr(leaf, "ndim"):
            specs.append(None)
            continue
        ps = _path_to_str(path)
        if ps.endswith(("qkv_proj", "gate_up_proj")):
            dims = (None, tp_axis)
        elif ps.endswith("qkv_bias"):
            dims = (tp_axis,)
        elif ps.endswith(("o_proj", "down_proj")):
            dims = (tp_axis, None)
        else:
            dims = (None,) * (leaf.ndim - 1)  # minus the stacked L dim
        specs.append(P("pp", *dims))
    return jax.tree_util.tree_unflatten(treedef, specs)


def init_llama_pp_state(model: "LlamaForCausalLM", optimizer, mesh=None):
    """(params, opt_state) for ``make_llama_pp_train_step``. Every leaf is
    a FRESH buffer (the train step donates its params, and donated aliases
    of module weights would delete them for later eval/checkpointing).

    With a mesh whose tp > 1 the stacked layer weights are converted to the
    tp-interleaved layout (training then stays in that layout; convert back
    for export with ``tp_shuffle_llama_params(..., inverse=True)``)."""
    _check_pp_model(model)
    params = _pp_params(model, copy=True)
    if mesh is not None and mesh.size("tp") > 1:
        params = tp_shuffle_llama_params(params, model.cfg, mesh.size("tp"))
    return params, optimizer.init(params)


def num_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token ≈ 6*N_params + attention term (for MFU)."""
    h, m, L, v = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers, cfg.vocab_size
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    d = h // nh
    per_layer = 2 * h * (nh + 2 * nkv) * d + 2 * nh * d * h + 2 * h * 2 * m + 2 * m * h
    n_matmul = L * per_layer + 2 * h * v  # fwd matmul FLOPs per token (x2 mult-add folded)
    attn = L * 2 * 2 * seq_len * nh * d  # qk^T and pv per token
    return 3.0 * (n_matmul + attn)  # fwd + 2x bwd
