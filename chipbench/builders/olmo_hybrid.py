"""Builds the program's Olmo-Hybrid model (``paddle_tpu.models.olmo_hybrid``)
from the benchmark's seeded weights (``chipbench.reference_olmo_hybrid``).
The one file that knows the program's fused layout for this family: a full
layer's qkv columns are [q | k | v]; a linear layer's ``qkvz_proj`` columns
are [q | k | v | z] and its ``ba_proj`` columns [b | a]; gate_up columns are
[gate | up]; the two branch norms are ``input_layernorm_2`` and
``post_attention_layernorm_2``. ``layer_types`` is handed over whole: the
program reads its first ``num_hidden_layers`` entries."""
import jax
import jax.numpy as jnp

from chipbench import reference_olmo_hybrid as ref


def program_config(cfg: dict, **overrides):
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("the program takes head_dim = hidden_size / "
                         "num_attention_heads")
    return OlmoHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        initializer_range=cfg["initializer_range"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        attention_bias=cfg["attention_bias"],
        layer_types=tuple(cfg["layer_types"]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        dtype=jnp.dtype(cfg["torch_dtype"]), **overrides)


def build(cfg: dict, seed: int, **overrides):
    """-> the program's model, every leaf drawn by the reference's module."""
    import paddle_tpu as pt
    from paddle_tpu.models.olmo_hybrid import OlmoHybridForCausalLM

    pcfg = program_config(cfg, **overrides)
    # the structure without its weights; the global rng it traced through
    # is reset afterwards
    model = jax.eval_shape(lambda: OlmoHybridForCausalLM(pcfg))
    pt.seed(seed & 0x7FFFFFFF)
    top = ref.make_top(seed, cfg)
    bb = model.model
    bb.embed_tokens, bb.norm.weight = top["embed"], top["norm"]
    model.lm_head = top["head"]
    for i, lyr in enumerate(bb.layers):
        w = ref.make_layer(seed, i, cfg)
        cat = lambda *names: jnp.concatenate([w[n] for n in names], axis=1)
        lyr.input_layernorm_2.weight = w["ln_attn_out"]
        lyr.post_attention_layernorm_2.weight = w["ln_mlp_out"]
        lyr.mlp.gate_up_proj = cat("w_gate", "w_up")
        lyr.mlp.down_proj = w["w_down"]
        if ref.kind(cfg, i) == ref.LINEAR:
            mix = lyr.linear_attn
            mix.qkvz_proj = cat("wq", "wk", "wv", "wz")
            mix.ba_proj = cat("wb", "wa")
            mix.conv_weight, mix.o_proj = w["conv_w"], w["wo"]
            mix.o_norm = w["o_norm"]
            mix.A_log, mix.dt_bias = w["A_log"], w["dt_bias"]
        else:
            att = lyr.self_attn
            att.qkv_proj, att.o_proj = cat("wq", "wk", "wv"), w["wo"]
            att.q_norm.weight, att.k_norm.weight = w["q_norm"], w["k_norm"]
    return model
