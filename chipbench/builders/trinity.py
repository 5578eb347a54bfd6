"""Builds the program's Trinity model (``paddle_tpu.models.trinity``) from
the benchmark's seeded weights (``chipbench.reference_trinity``). The one
file that knows the program's layout for this family: the attention's
fused ``qkv_proj`` columns are [q | k | v], its output gate ``gate_proj``,
the per-head norms ``q_norm`` / ``k_norm``; the block's four norms are
``input_layernorm`` (ln1), ``input_layernorm_2`` (ln2),
``post_attention_layernorm`` (ln3), ``post_attention_layernorm_2`` (ln4);
gate_up columns are [gate | up], in the dense MLP, the shared expert and
each expert of the stacks (``moe.experts.gate_up`` [experts, hidden, 2 x
width], ``moe.experts.down``); the router is ``moe.gate_w`` (float32) with
``moe.gate_bias`` the expert bias."""
import jax
import jax.numpy as jnp

from chipbench import reference_trinity as ref

PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "max_position_embeddings", "rms_norm_eps", "sliding_window",
             "global_attn_every_n_layers", "num_dense_layers", "num_experts",
             "num_experts_per_tok", "num_shared_experts", "score_func",
             "route_norm", "route_scale", "n_group", "topk_group",
             "mup_enabled", "initializer_range", "tie_word_embeddings")


def program_config(cfg: dict, **overrides):
    from paddle_tpu.models.trinity import TrinityConfig
    return TrinityConfig(**{
        **{k: cfg[k] for k in PUBLISHED},
        "rope_theta": float(cfg["rope_theta"]),
        "rope_scaling": cfg["rope_scaling"],
        "layer_types": tuple(cfg["layer_types"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]), **overrides})


def build(cfg: dict, seed: int, **overrides):
    """-> the program's model, every leaf drawn by the reference's module."""
    import paddle_tpu as pt
    from paddle_tpu.models.trinity import TrinityForCausalLM

    pcfg = program_config(cfg, **overrides)
    # the structure without its weights; the global rng it traced through
    # is reset afterwards
    model = jax.eval_shape(lambda: TrinityForCausalLM(pcfg))
    pt.seed(seed & 0x7FFFFFFF)
    top = ref.make_top(seed, cfg)
    model.embed_tokens, model.norm.weight = top["embed"], top["norm"]
    model.lm_head = top["head"]
    bias = jnp.asarray(ref.score_bias(cfg))
    for i, lyr in enumerate(model.layers):
        w = ref.make_layer(seed, i, cfg)
        cat = lambda *names: jnp.concatenate([w[n] for n in names], axis=-1)
        lyr.input_layernorm.weight = w["ln1"]
        lyr.input_layernorm_2.weight = w["ln2"]
        lyr.post_attention_layernorm.weight = w["ln3"]
        lyr.post_attention_layernorm_2.weight = w["ln4"]
        att = lyr.self_attn
        att.qkv_proj = cat("wq", "wk", "wv")
        att.gate_proj, att.o_proj = w["wg"], w["wo"]
        att.q_norm.weight, att.k_norm.weight = w["q_norm"], w["k_norm"]
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
        # a layer at a time: the host runs far ahead of the device, and
        # the layers' raw tensors would all be alive beside their fused
        # copies (15.1 of 16 GB at the peak of a build that hit the compile
        # cache; PERF.md section 6, PR 44)
        del w
        jax.block_until_ready(blk.moe.experts.gate_up)
    return model
