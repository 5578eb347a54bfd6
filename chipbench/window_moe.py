"""The benchmark's own arithmetic for a model whose K/V layers are of two
kinds, window and full attention, over expert layers that hold every expert
(``configs/trinity-mini.serve-d5.json``). From the configuration's sizes
alone; ``tests/test_window_moe.py`` holds that file's numbers by hand."""
from chipbench.latent_moe import _itemsize, routed_calls  # noqa: F401

WINDOW, FULL = "sliding_attention", "full_attention"


def layers(cfg: dict, kind: str) -> int:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]]).count(kind)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def attention_matmul_params(cfg: dict) -> int:
    """q, k, v, the output gate and the output projection."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return e * (nh + 2 * nkv) * d + 2 * e * nh * d


def attention_params(cfg: dict) -> int:
    """With the q and the k norm's gains (one vector of a head each)."""
    return attention_matmul_params(cfg) + 2 * cfg["head_dim"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def dense_layer_params(cfg: dict) -> int:
    """Attention, the block's four norms, the dense MLP."""
    return (attention_params(cfg) + 4 * cfg["hidden_size"]
            + 3 * cfg["hidden_size"] * cfg["intermediate_size"])


def expert_layer_params(cfg: dict) -> int:
    """Attention, the four norms, every expert, the shared experts, the
    router and its expert bias."""
    return (attention_params(cfg) + 4 * cfg["hidden_size"]
            + (cfg["num_experts"] + cfg["num_shared_experts"])
            * expert_params(cfg) + router_params(cfg) + cfg["num_experts"])


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def model_params(cfg: dict) -> int:
    """Everything the chip holds: the layers, the final norm, the embedding
    and the untied head."""
    return (cfg["num_dense_layers"] * dense_layer_params(cfg)
            + expert_layers(cfg) * expert_layer_params(cfg)
            + cfg["hidden_size"] + 2 * head_params(cfg))


def weight_bytes(cfg: dict) -> int:
    """As it lies in HBM: the routers' matrices and biases are float32."""
    f32 = expert_layers(cfg) * (router_params(cfg) + cfg["num_experts"])
    return model_params(cfg) * _itemsize(cfg) + f32 * (4 - _itemsize(cfg))


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """K and V of one token in one layer."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _itemsize(cfg))


def kv_bytes_per_token(cfg: dict, kind: str) -> int:
    """K and V of one token over the layers of ``kind``: what the token
    costs in that kind's block space."""
    return layers(cfg, kind) * kv_bytes_per_token_layer(cfg)


def token_matmul_params(cfg: dict) -> int:
    """Matmul parameters every token multiplies whatever it routes:
    attention in every layer, the dense MLPs, the shared experts and the
    routers. The routed experts ride ``routed_pairs``, the head its own
    count of rows."""
    return (cfg["num_hidden_layers"] * attention_matmul_params(cfg)
            + cfg["num_dense_layers"] * 3 * cfg["hidden_size"]
            * cfg["intermediate_size"]
            + expert_layers(cfg) * (cfg["num_shared_experts"]
                                    * expert_params(cfg)
                                    + router_params(cfg)))


def attention_flops_per_key(cfg: dict) -> float:
    """One (query, key) pair in one layer: q.k and p.v over a head's dims
    for every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def window_keys(offset: float, n: float, window: int) -> float:
    """Keys the ``n`` queries at positions ``offset .. offset + n - 1`` of
    one row read in a window layer: query ``t`` reads ``min(t + 1,
    window)``."""
    ramp = min(max(window - offset, 0), n)      # queries still under it
    return ramp * offset + ramp * (ramp + 1) / 2.0 + (n - ramp) * window


def causal_keys(offset: float, n: float) -> float:
    """The same in a full layer: query ``t`` reads ``t + 1``."""
    return n * offset + n * (n + 1) / 2.0


def attention_flops(cfg: dict, keys_window: float, keys_full: float) -> float:
    """``keys_*``: (query, key) pairs of ONE layer of each kind."""
    return attention_flops_per_key(cfg) * (
        layers(cfg, WINDOW) * keys_window + layers(cfg, FULL) * keys_full)


def forward_flops(cfg: dict, tokens: float, head_rows: float,
                  keys_window: float, keys_full: float,
                  routed_pairs: float) -> float:
    """Forward FLOPs of ``tokens`` tokens of which ``head_rows`` went
    through the head, that attended ``keys_window`` / ``keys_full`` (query,
    key) pairs in a layer of each kind and sent ``routed_pairs`` (token,
    expert) pairs to experts (summed over the expert layers)."""
    return (2.0 * tokens * token_matmul_params(cfg)
            + 2.0 * head_rows * head_params(cfg)
            + attention_flops(cfg, keys_window, keys_full)
            + 2.0 * routed_pairs * expert_params(cfg))


def grouped_floor_seconds(cfg: dict, routed_pairs: float, experts_hit: float,
                          peak: dict) -> float:
    """The least a call's grouped products can take: the larger of their
    FLOPs at the chip's bf16 peak and, at its HBM rate, the weights of the
    experts that got at least one token (each streamed once)."""
    return max(2.0 * routed_pairs * expert_params(cfg)
               / peak["bf16_flops_per_s"],
               experts_hit * expert_params(cfg) * _itemsize(cfg)
               / peak["hbm_bytes_per_s"])


def kv_read_bytes(cfg: dict, blocks_window: float, blocks_full: float,
                  block: int) -> float:
    """K/V bytes the paged kernels read for ``blocks_*`` pool blocks of
    ONE layer of each kind."""
    return (layers(cfg, WINDOW) * blocks_window
            + layers(cfg, FULL) * blocks_full) * block \
        * kv_bytes_per_token_layer(cfg)


def decode_tick_bytes(cfg: dict, blocks_window: float, blocks_full: float,
                      block: int, experts_hit: float) -> float:
    """Bytes a decode tick cannot avoid moving: every weight outside the
    routed experts once, the head among them (the embedding is gathered: a
    row a slot, not counted), the weights of the ``experts_hit`` experts
    that got a token, and the K/V of the live blocks in each space."""
    routed = expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    always = weight_bytes(cfg) - (head_params(cfg) + routed) * _itemsize(cfg)
    return (always + experts_hit * expert_params(cfg) * _itemsize(cfg)
            + kv_read_bytes(cfg, blocks_window, blocks_full, block))


def space_ticks(events) -> list:
    """The args of the traced ``serving.decode`` spans that count each
    space's blocks (none on a program without two block spaces)."""
    return [e["args"] for e in events if e["name"] == "serving.decode"
            and "kv_blocks_window" in e.get("args", {})]


def chunk_calls(events) -> list:
    """[(offset, tokens, kv_blocks_window, kv_blocks_full), ...] of the
    traced ``exe.prefill_chunk`` spans that count each space's blocks (one
    live row a call: ``ctx_tokens`` less ``useful`` is its offset)."""
    out = []
    for e in events:
        a = e.get("args", {})
        if e["name"] == "exe.prefill_chunk" and "kv_blocks_window" in a:
            out.append((a["ctx_tokens"] - a["useful"], a["useful"],
                        a["kv_blocks_window"], a["kv_blocks_full"]))
    return out
