"""Builds the program's LLaMA-shaped model (``paddle_tpu.models``) from the
benchmark's seeded weights. The only file that knows the program's fused
layout: qkv columns are [q | k | v], gate_up columns are [gate | up]."""
import jax
import jax.numpy as jnp

from chipbench import weights


def program_config(cfg: dict, **overrides):
    from paddle_tpu.models.mistral import MistralConfig
    return MistralConfig(
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
        dtype=jnp.dtype(cfg["torch_dtype"]), **overrides)


# the program's leaves by comparison group, and the published tensors (as
# ``weights.py`` and ``reference.py`` name them) that each group fuses
GROUPS = {"qkv": ("wq", "wk", "wv"), "o": ("wo",),
          "gate_up": ("w_gate", "w_up"), "down": ("w_down",),
          "ln_attn": ("ln_attn",), "ln_mlp": ("ln_mlp",)}


def program_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer i's tensors in the program's layout, by group."""
    w = weights.make_layer(seed, i, cfg)
    return {g: w[names[0]] if len(names) == 1
            else jnp.concatenate([w[n] for n in names], axis=1)
            for g, names in GROUPS.items()}


def leaves(tree) -> dict:
    """{"L<i>.<group>" | "embed" | "norm" | "head": leaf} of a tree shaped
    like the program's model (the model, a moment, the master weights)."""
    out = {"embed": tree.model.embed_tokens, "norm": tree.model.norm.weight,
           "head": tree.lm_head}
    for i, lyr in enumerate(tree.model.layers):
        out.update({f"L{i}.qkv": lyr.self_attn.qkv_proj,
                    f"L{i}.o": lyr.self_attn.o_proj,
                    f"L{i}.gate_up": lyr.mlp.gate_up_proj,
                    f"L{i}.down": lyr.mlp.down_proj,
                    f"L{i}.ln_attn": lyr.input_layernorm.weight,
                    f"L{i}.ln_mlp": lyr.post_attention_layernorm.weight})
    return out


def build(cfg: dict, seed: int, **overrides):
    """-> the program's model, every leaf drawn by ``chipbench.weights``."""
    import paddle_tpu as pt
    from paddle_tpu.models.mistral import MistralForCausalLM

    pcfg = program_config(cfg, **overrides)
    if pcfg.hidden_size // pcfg.num_attention_heads != cfg["head_dim"]:
        raise ValueError("head_dim is not hidden_size / num_attention_heads")
    # the structure without its weights; the global rng it traced through
    # is reset afterwards
    model = jax.eval_shape(lambda: MistralForCausalLM(pcfg))
    pt.seed(seed & 0x7FFFFFFF)
    top = weights.make_top(seed, cfg)
    model.model.embed_tokens = top["embed"]
    model.model.norm.weight = top["norm"]
    model.lm_head = top["head"]
    for i, lyr in enumerate(model.model.layers):
        w = program_layer(cfg, seed, i)
        lyr.input_layernorm.weight = w["ln_attn"]
        lyr.post_attention_layernorm.weight = w["ln_mlp"]
        lyr.self_attn.qkv_proj = w["qkv"]
        lyr.self_attn.o_proj = w["o"]
        lyr.mlp.gate_up_proj = w["gate_up"]
        lyr.mlp.down_proj = w["down"]
    return model
