"""Builds the program's Kimi-K2 model (``paddle_tpu.models.kimi_k2``) from
the benchmark's seeded weights (``chipbench.reference_kimi_k2``). The one
file that knows the program's layout for this family: the MLA projections
under the published names (``kv_a_proj`` is ``kv_a_proj_with_mqa``: [c_kv |
k_r]; a head's ``q_b_proj`` columns are [nope | rope], its ``kv_b_proj``
columns [k_nope | v]); gate_up columns are [gate | up], in the dense MLP,
the shared expert and each expert of the stacks (``moe.experts.gate_up``
[held, hidden, 2 x width], ``moe.experts.down``); the router is
``moe.gate_w`` (float32) with ``moe.gate_bias`` the selection bias. The
model is built with ``held_experts`` = the configuration's share and the
router at its published width."""
import jax
import jax.numpy as jnp

from chipbench import reference_kimi_k2 as ref

PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads",
             "max_position_embeddings", "rms_norm_eps", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_shared_experts", "num_experts_per_tok",
             "first_k_dense_replace", "moe_layer_freq", "n_group",
             "topk_group", "norm_topk_prob", "routed_scaling_factor",
             "scoring_func", "topk_method", "initializer_range",
             "tie_word_embeddings")


def program_config(cfg: dict, **overrides):
    from paddle_tpu.models.kimi_k2 import KimiK2Config
    return KimiK2Config(
        **{k: cfg[k] for k in PUBLISHED}, rope_theta=float(cfg["rope_theta"]),
        rope_scaling=dict(cfg["rope_scaling"]),
        n_routed_experts=ref.router_experts(cfg), held_experts=ref.held(cfg),
        dtype=jnp.dtype(cfg["torch_dtype"]), **overrides)


def build(cfg: dict, seed: int, **overrides):
    """-> the program's model, every leaf drawn by the reference's module."""
    import paddle_tpu as pt
    from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM

    pcfg = program_config(cfg, **overrides)
    # the structure without its weights; the global rng it traced through
    # is reset afterwards
    model = jax.eval_shape(lambda: KimiK2ForCausalLM(pcfg))
    pt.seed(seed & 0x7FFFFFFF)
    top = ref.make_top(seed, cfg)
    model.embed_tokens, model.norm.weight = top["embed"], top["norm"]
    model.lm_head = top["head"]
    bias = jnp.asarray(ref.score_bias(cfg))
    for i, lyr in enumerate(model.layers):
        w = ref.make_layer(seed, i, cfg)
        cat = lambda a, b: jnp.concatenate([w[a], w[b]], axis=-1)
        lyr.input_layernorm.weight = w["ln_attn"]
        lyr.post_attention_layernorm.weight = w["ln_mlp"]
        att = lyr.self_attn
        att.q_a_proj, att.q_b_proj = w["w_qa"], w["w_qb"]
        att.kv_a_proj, att.kv_b_proj = w["w_kva"], w["w_kvb"]
        att.q_a_layernorm.weight = w["q_norm"]
        att.kv_a_layernorm.weight = w["kv_norm"]
        att.o_proj = w["wo"]
        if ref.is_dense(cfg, i):
            lyr.mlp.gate_up_proj = cat("w_gate", "w_up")
            lyr.mlp.down_proj = w["w_down"]
            continue
        blk = lyr.mlp
        blk.shared.gate_up_proj = cat("shared_gate", "shared_up")
        blk.shared.down_proj = w["shared_down"]
        blk.moe.gate_w, blk.moe.gate_bias = w["w_router"], bias
        blk.moe.experts.gate_up = cat("experts_gate", "experts_up")
        blk.moe.experts.down = w["experts_down"]
    return model
