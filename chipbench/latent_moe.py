"""The benchmark's own arithmetic for a model with latent attention (MLA)
and expert layers of which one chip holds a share (``configs/kimi-k2-
instruct.serve-ep32-d7.json``). From the configuration's sizes alone; for
that file the numbers are those of ISSUE 41's table (``tests/
test_latent_moe.py`` holds them by hand). ``cfg["n_routed_experts"]`` counts
the experts HELD here; the router's width is the published count."""
import numpy as np


def _itemsize(cfg: dict) -> int:
    return 2 if cfg["torch_dtype"] == "bfloat16" else np.dtype(
        cfg["torch_dtype"]).itemsize


def router_experts(cfg: dict) -> int:
    return int(cfg["published"]["n_routed_experts"])


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def attention_matmul_params(cfg: dict) -> int:
    """q_a, q_b, kv_a (the latent and the shared rotated key), kv_b, o."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (e * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + e * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * e)


def attention_params(cfg: dict) -> int:
    """With the two latent norms' gains."""
    return (attention_matmul_params(cfg) + cfg["q_lora_rank"]
            + cfg["kv_lora_rank"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * router_experts(cfg)


def expert_layer_params(cfg: dict, held: int = None) -> int:
    """An expert layer with ``held`` routed experts (None: those the file
    holds): MLA, the two block norms, the shared experts, the router and
    its selection bias, the experts."""
    held = cfg["n_routed_experts"] if held is None else held
    return (attention_params(cfg) + 2 * cfg["hidden_size"]
            + cfg["n_shared_experts"] * expert_params(cfg)
            + router_params(cfg) + router_experts(cfg)
            + held * expert_params(cfg))


def dense_layer_params(cfg: dict) -> int:
    return (attention_params(cfg) + 2 * cfg["hidden_size"]
            + 3 * cfg["hidden_size"] * cfg["intermediate_size"])


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def model_params(cfg: dict) -> int:
    """Everything the chip holds: the layers, the final norm, the embedding
    and the untied head over the vocabulary's slice."""
    return (cfg["first_k_dense_replace"] * dense_layer_params(cfg)
            + expert_layers(cfg) * expert_layer_params(cfg)
            + cfg["hidden_size"] + 2 * head_params(cfg))


def weight_bytes(cfg: dict) -> int:
    """As it lies in HBM: the router's matrix and bias are float32."""
    f32 = expert_layers(cfg) * (router_params(cfg) + router_experts(cfg))
    return model_params(cfg) * _itemsize(cfg) + f32 * (4 - _itemsize(cfg))


def cache_bytes_per_token_layer(cfg: dict) -> int:
    """The model's latent row: the latent and the shared rotated key (a
    pool may pad a row to whole lanes; the model's bytes are these)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * _itemsize(cfg)


def cache_bytes_per_token(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * cache_bytes_per_token_layer(cfg)


def token_matmul_params(cfg: dict) -> int:
    """Matmul parameters every token multiplies whatever it routes: MLA in
    every layer, the dense MLPs, the shared experts and the routers, the
    head. The routed experts ride ``routed_pairs``."""
    return (cfg["num_hidden_layers"] * attention_matmul_params(cfg)
            + cfg["first_k_dense_replace"] * 3 * cfg["hidden_size"]
            * cfg["intermediate_size"]
            + expert_layers(cfg) * (cfg["n_shared_experts"]
                                    * expert_params(cfg)
                                    + router_params(cfg))
            + head_params(cfg))


def attention_flops_per_key(cfg: dict) -> float:
    """What the mathematics asks of one (query, cached key) pair over all
    layers, in the expanded form: q.k over nope + rope and p.v over
    v_head_dim for every head. (The absorbed form a program may run spends
    (2 rank + rope) / (nope + rope + v) times that.)"""
    per_head = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                + cfg["v_head_dim"])
    return 2.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * per_head


def forward_flops(cfg: dict, tokens: float, keys: float,
                  routed_pairs: float) -> float:
    """Forward FLOPs of ``tokens`` tokens that attended ``keys`` (query,
    key) pairs in all and sent ``routed_pairs`` (token, expert) pairs to
    experts held here (summed over the expert layers)."""
    return (2.0 * tokens * token_matmul_params(cfg)
            + keys * attention_flops_per_key(cfg)
            + 2.0 * routed_pairs * expert_params(cfg))


def even_routed_pairs_per_token(cfg: dict) -> float:
    """Pairs a token sends to the held experts under an even router."""
    return (expert_layers(cfg) * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / router_experts(cfg))


def grouped_floor_seconds(cfg: dict, routed_pairs: float, experts_hit: float,
                          peak: dict) -> float:
    """The least a call's grouped products can take: the larger of their
    FLOPs at the chip's bf16 peak and, at its HBM rate, the weights of the
    experts that got at least one token (each streamed once)."""
    return max(2.0 * routed_pairs * expert_params(cfg)
               / peak["bf16_flops_per_s"],
               experts_hit * expert_params(cfg) * _itemsize(cfg)
               / peak["hbm_bytes_per_s"])


def decode_tick_bytes(cfg: dict, ctx_tokens: float, experts_hit: float,
                      cache_layers: int) -> float:
    """Bytes a decode tick cannot avoid moving: every weight outside the
    routed experts once (the embedding is gathered: a row a slot, not
    counted), the weights of the ``experts_hit`` held experts that got a
    token, and the latent rows of ``ctx_tokens`` cached tokens in each of
    ``cache_layers`` layers."""
    held = expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
    always = weight_bytes(cfg) - (head_params(cfg) + held) * _itemsize(cfg)
    return (always + experts_hit * expert_params(cfg) * _itemsize(cfg)
            + ctx_tokens * cache_layers * cache_bytes_per_token_layer(cfg))


def routed_calls(events) -> list:
    """[(routed_pairs, experts_hit), ...] of every program call the spans
    hold counts for: a decode tick's on ``serving.decode``, a prefill
    call's on the ``exe.routed`` span emitted at the next wait."""
    return [(e["args"]["routed_pairs"], e["args"]["experts_hit"])
            for e in events if e["name"] in ("serving.decode", "exe.routed")
            and "routed_pairs" in e.get("args", {})]
