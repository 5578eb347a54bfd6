"""The benchmark's own arithmetic: what the model's mathematics requires,
from the configuration's published sizes. Nothing recomputed is counted,
the embedding lookup is not a matmul, and causal attention is counted at
the half it needs (a query at position s attends s + 1 keys)."""
import json
from pathlib import Path


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers held and the output head."""
    h, m, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = h * (nh + 2 * nkv) * d + nh * d * h + 3 * h * m
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """One token's forward pass attending ``context`` keys: 2 per matmul
    parameter, and q.k and p.v over the context for every head and layer."""
    attn = 2 * 2 * context * cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * matmul_params(cfg) + cfg["num_hidden_layers"] * attn


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (twice the forward) of a causal sequence: the
    mean context of its tokens is half its length."""
    return 3.0 * forward_flops_per_token(cfg, seq_len / 2.0)


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       "in chipbench/peaks.json: add it with its source")
    return table[device_kind]
