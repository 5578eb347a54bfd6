"""Builds the program's Ouro model (``paddle_tpu.models.ouro``) from the
benchmark's seeded weights (``chipbench.reference_ouro``). The one file that
knows the program's fused layout for this family: qkv columns are
[q | k | v], gate_up columns are [gate | up]; the two branch norms are
``input_layernorm_2`` and ``post_attention_layernorm_2``, the exit gate
``early_exit_gate_w`` / ``_b`` on the backbone."""
import jax
import jax.numpy as jnp

from chipbench import reference_ouro as ref


def program_config(cfg: dict, **overrides):
    from paddle_tpu.models.ouro import OuroConfig
    return OuroConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        sliding_window=cfg["sliding_window"],
        initializer_range=cfg["initializer_range"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        total_ut_steps=cfg["total_ut_steps"],
        early_exit_threshold=cfg["early_exit_threshold"],
        dtype=jnp.dtype(cfg["torch_dtype"]), **overrides)


def build(cfg: dict, seed: int, **overrides):
    """-> the program's model, every leaf drawn by ``reference_ouro``."""
    import paddle_tpu as pt
    from paddle_tpu.models.ouro import OuroForCausalLM

    pcfg = program_config(cfg, **overrides)
    # the structure without its weights; the global rng it traced through
    # is reset afterwards
    model = jax.eval_shape(lambda: OuroForCausalLM(pcfg))
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    width = model.model.layers[0].self_attn.qkv_proj.shape[1]
    if width != (nh + 2 * nkv) * d:
        raise ValueError(
            f"the program's q/k/v projection is {width} wide, the "
            f"configuration's heads ask for (num_attention_heads + 2 x "
            f"num_key_value_heads) x head_dim = {(nh + 2 * nkv) * d}")
    pt.seed(seed & 0x7FFFFFFF)
    top = ref.make_top(seed, cfg)
    bb = model.model
    bb.embed_tokens, bb.norm.weight = top["embed"], top["norm"]
    bb.early_exit_gate_w, bb.early_exit_gate_b = top["exit_w"], top["exit_b"]
    model.lm_head = top["head"]
    cat = lambda *names: jnp.concatenate([w[n] for n in names], axis=1)
    for i, lyr in enumerate(bb.layers):
        w = ref.make_layer(seed, i, cfg)
        lyr.input_layernorm.weight = w["ln_attn"]
        lyr.input_layernorm_2.weight = w["ln_attn_out"]
        lyr.post_attention_layernorm.weight = w["ln_mlp"]
        lyr.post_attention_layernorm_2.weight = w["ln_mlp_out"]
        lyr.self_attn.qkv_proj = cat("wq", "wk", "wv")
        lyr.self_attn.o_proj = w["wo"]
        lyr.mlp.gate_up_proj = cat("w_gate", "w_up")
        lyr.mlp.down_proj = w["w_down"]
    return model
