"""The benchmark's own arithmetic for a model whose layers are of two kinds
(``layer_types``: softmax attention, or the gated delta rule with a
recurrent state and no K/V). From the configuration's sizes alone; for the
cut Olmo-Hybrid-7B of ``configs/olmo-hybrid-7b.serve-d16.json`` the numbers
are those of ISSUE 35 (``tests/test_hybrid.py`` holds them by hand)."""
import numpy as np

LINEAR, FULL = "linear_attention", "full_attention"
CHUNK = 64      # tokens a chunk of the chunked rule, as the kernel is built


def kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def layers(cfg: dict, kind: str) -> int:
    return kinds(cfg).count(kind)


def _itemsize(cfg: dict) -> int:
    return 2 if cfg["torch_dtype"] == "bfloat16" else np.dtype(
        cfg["torch_dtype"]).itemsize


def _linear_dims(cfg: dict):
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def conv_channels(cfg: dict) -> int:
    h, dk, dv = _linear_dims(cfg)
    return h * (2 * dk + dv)


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    """Matmul parameters of one layer's token mixer. Linear: q, k, v and
    the gate z, the output projection, beta and the decay (the depthwise
    convolution, the norms, ``A_log`` and ``dt_bias`` are no matmuls).
    Full: q, k, v and the output projection."""
    e = cfg["hidden_size"]
    if kind == LINEAR:
        h, dk, dv = _linear_dims(cfg)
        return e * (conv_channels(cfg) + h * dv) + h * dv * e + 2 * e * h
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return e * (nh + 2 * nkv) * d + nh * d * e


def layer_matmul_params(cfg: dict, kind: str) -> int:
    return mixer_matmul_params(cfg, kind) + mlp_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def stack_matmul_params(cfg: dict) -> int:
    """Every layer's matmul parameters and the output head's (the embedding
    is a lookup)."""
    return (sum(layer_matmul_params(cfg, k) for k in kinds(cfg))
            + head_params(cfg))


def weight_bytes(cfg: dict) -> int:
    """The model as it lies in HBM: the stack, and the embedding."""
    return (stack_matmul_params(cfg) + head_params(cfg)) * _itemsize(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over the full layers."""
    return (layers(cfg, FULL) * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * _itemsize(cfg))


def recurrent_bytes_per_layer(cfg: dict) -> int:
    """``S`` of one slot in one linear layer: a head in ``R^{d_k x d_v}``,
    float32."""
    h, dk, dv = _linear_dims(cfg)
    return h * dk * dv * 4


def state_bytes_per_layer(cfg: dict) -> int:
    """One slot's state in one linear layer: ``S``, and the convolution's
    last ``K - 1`` inputs at the model's dtype."""
    return (recurrent_bytes_per_layer(cfg)
            + (cfg["linear_conv_kernel_dim"] - 1) * conv_channels(cfg)
            * _itemsize(cfg))


def state_bytes_per_slot(cfg: dict) -> int:
    return layers(cfg, LINEAR) * state_bytes_per_layer(cfg)


def rule_flops_per_token(cfg: dict, chunk: int = CHUNK) -> float:
    """The chunked rule's multiply-adds for one token in one linear layer,
    2 FLOPs each: with C tokens a chunk, a head's ``K K^T`` and ``Q K^T``
    (C d_k each), the solved triangle applied to K and V (C d_k, C d_v),
    the chunk against the state (``W S``, ``Q S``: d_k d_v each), its own
    outputs (C d_v) and the state's hand-over (d_k d_v). The triangular
    solve itself (C^2 a token, in float32) is counted once, as the
    substitution it replaces."""
    h, dk, dv = _linear_dims(cfg)
    macs = chunk * (3 * dk + 2 * dv) + 3 * dk * dv + chunk * chunk
    return 2.0 * h * macs


def rule_bytes_per_token(cfg: dict) -> float:
    """What the rule must move for one token in one linear layer: q, k and
    v in at the model's dtype, the output back in float32, the decay and
    beta a head in float32. The state stays on the chip for a whole row."""
    h, dk, dv = _linear_dims(cfg)
    return h * ((2 * dk + dv) * _itemsize(cfg) + dv * 4 + 2 * 4)


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """One token's forward pass at ``context`` keys: 2 per matmul parameter
    of the stack and the head; q.k and p.v over the context for every head
    of every full layer; the rule in every linear layer."""
    attn = 2 * 2 * context * cfg["num_attention_heads"] * cfg["head_dim"]
    return (2.0 * stack_matmul_params(cfg) + layers(cfg, FULL) * attn
            + layers(cfg, LINEAR) * rule_flops_per_token(cfg))


def decode_tick_bytes(cfg: dict, kv_tokens: float, slots: float,
                      cache_layers: int = None,
                      state_layers: int = None) -> float:
    """What one decode tick must move: the stack's matmul weights and the
    head once, the K/V of ``kv_tokens`` live tokens in each cache layer,
    and the state of ``slots`` running slots read and written in each
    state layer."""
    full = layers(cfg, FULL) if cache_layers is None else cache_layers
    lin = layers(cfg, LINEAR) if state_layers is None else state_layers
    kv = (kv_tokens * full * 2 * cfg["num_key_value_heads"]
          * cfg["head_dim"] * _itemsize(cfg))
    return (stack_matmul_params(cfg) * _itemsize(cfg) + kv
            + 2.0 * slots * lin * state_bytes_per_layer(cfg))


def kernel_seconds(run: dict, kernel: str):
    """Device seconds of the operation the reduced trace prints as
    ``kernel``; None on an untraced run and where it holds none."""
    t = run.get("trace")
    if not t:
        return None
    return next((s for name, s in t["device_ops"]
                 if name.lstrip("%") == kernel), None)


def peaks(run: dict) -> dict:
    """The published peaks of the chip a run was made on (``peaks.json``)."""
    from chipbench import flops
    kind = run.get("device_kind")
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    return flops.peaks(kind)
