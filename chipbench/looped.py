"""The benchmark's own arithmetic for a model whose layers run several
times a token (``total_ut_steps`` passes; 1 where the configuration has no
such key, so every function here reads a one-pass model as ``flops.py``
does). From the configuration's published sizes alone."""
import numpy as np


def passes(cfg: dict) -> int:
    return int(cfg.get("total_ut_steps", 1))


def cache_layers(cfg: dict) -> int:
    """K/V layers a token keeps: one for every (pass, layer) pair."""
    return cfg["num_hidden_layers"] * passes(cfg)


def layer_matmul_params(cfg: dict) -> int:
    h, m, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * (nh + 2 * nkv) * d + nh * d * h + 3 * h * m


def _itemsize(cfg: dict) -> int:
    return 2 if cfg["torch_dtype"] == "bfloat16" else np.dtype(
        cfg["torch_dtype"]).itemsize


def matmul_weight_bytes_per_tick(cfg: dict) -> int:
    """Bytes of matmul weights one decode tick has to stream from HBM,
    whatever its batch: every layer's once a pass, and the output head's
    once (the embedding is a lookup; norms and the gate are not counted)."""
    params = (passes(cfg) * cfg["num_hidden_layers"]
              * layer_matmul_params(cfg)
              + cfg["hidden_size"] * cfg["vocab_size"])
    return params * _itemsize(cfg)


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """K and V bytes of one token in one cache layer, at the
    configuration's dtype."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _itemsize(cfg))


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """One token's forward pass attending ``context`` keys, the passes
    counted: 2 per matmul parameter a pass (the head once), and q.k and p.v
    over the context for every head, layer and pass."""
    attn = 2 * 2 * context * cfg["num_attention_heads"] * cfg["head_dim"]
    return (passes(cfg) * cfg["num_hidden_layers"]
            * (2.0 * layer_matmul_params(cfg) + attn)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def hbm_bytes_per_s(run: dict) -> float:
    """The published HBM bytes a second of the chip a run was made on
    (``peaks.json``): the record's ``device_kind`` where its driver gives
    one, else the first device of this process, which holds it already."""
    from chipbench import flops
    kind = run.get("device_kind")
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    return flops.peaks(kind)["hbm_bytes_per_s"]
