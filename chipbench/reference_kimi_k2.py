"""Plain reference of the Kimi-K2 decoder (``model_type: kimi_k2``,
moonshotai Kimi-K2-Instruct: the DeepSeek-V3 block), written from the
published description and independent of ``paddle_tpu``. RMSNorm ``n(x; g) =
x * rsqrt(mean(x^2) + eps) * g``, no biases, pre-norm:

    x = x + MLA(n(x; ln_attn));   x = x + F(n(x; ln_mlp))

**MLA**, expanded form only (no cache, no absorption), for a row's normed
input ``u`` [S, hidden], H heads:

    c_q = n(u w_qa; q_norm);  q = c_q w_qb        [q_nope (nope) | q_rope (rope)] a head
    [c_kv | k_r] = u w_kva;   c_kv = n(c_kv; kv_norm);  k_r = rope(k_r)
    [k_nope_h | v_h] = c_kv w_kvb                 (nope + v_head_dim a head)
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + rope(q_rope_h)(t).k_r(s)) * scale
    out = concat_h(softmax_s<=t(score_h) v_h) wo

``scale = (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor)
+ 1`` (1.34657 and 0.130861 as published). Rope is YaRN over the ``rope``
dims: per pair the interpolated frequency ``f / factor`` and the plain ``f``
blended by the published linear ramp between the two correction dims
(``beta_fast``, ``beta_slow`` as the row gives them, with the ``+0.001``
where the bounds meet); cos and sin unscaled (``mscale == mscale_all_dim``).
The rotation pairs dim i with dim i + rope/2 of the rope dims AS THEY LIE:
the published code de-interleaves them first, a fixed permutation of
columns that seeded random weights cannot tell apart (``assumed``).

**F**: a SwiGLU MLP of ``intermediate_size`` in the first
``first_k_dense_replace`` layers, the expert layer after them:

    s = sigmoid(u w_router)               float32, all ``published`` experts
    choice = top-k(s + b)                 b: e_score_correction_bias
    g_e = routed_scaling_factor * s_e / (sum_{e in choice} s_e + 1e-20)
    F(u) = Shared(u) + sum_{e in choice, e held} g_e Expert_e(u)

**The share.** ``cfg["held_experts"]`` are the global ids of the routed
experts this chip holds; the router keeps its published width, and the
experts that are absent add nothing, here as in the program: that partial
result is what the next layer reads. The vocabulary is the slice the
configuration gives.

float32 throughout under ``jax.default_matmul_precision("highest")``; one
row at a time over its full sequence, attention a block of queries at a
time and logits at the kept positions alone, so that a 16k-token row fits.
A held expert is computed over the tokens routed to it, gathered up to a
bound (``expert_cap``: ``EXPERT_ROWS`` times the share of a row an even
router sends one expert; where an expert got more, ``forward`` runs the
layer again with the bound the fullest expert needs).

Weights and their names are here too (``layer_shapes``, ``make_layer``,
``make_top``), through ``weights._draw_all``: a tensor is a pure function
of (seed, layer, name). The router's matrix is float32, as published; the
selection bias is NOT drawn: ``score_bias`` is a formula from the file,
the same under every seed, so the same experts live under every seed.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import _rms

Q_BLOCK = 256           # queries a block of the reference's attention
EXPERT_ROWS = 6         # a held expert computes this many times its even share


# ------------------------------------------------------------------ sizes
def router_experts(cfg: dict) -> int:
    """The router's width: the published count, not the experts held."""
    return int(cfg["published"]["n_routed_experts"])


def held(cfg: dict) -> tuple:
    return tuple(int(e) for e in cfg["held_experts"])


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def expert_cap(cfg: dict, s: int) -> int:
    """Rows a held expert's gather holds for a row of ``s`` tokens."""
    even = s * cfg["num_experts_per_tok"] / router_experts(cfg)
    return int(min(s, max(8, math.ceil(EXPERT_ROWS * even))))


def score_bias(cfg: dict) -> np.ndarray:
    """``e_score_correction_bias``: ``amplitude * (-1)^e``, float32."""
    amp = float(cfg["e_score_correction_bias"]["amplitude"])
    e = np.arange(router_experts(cfg))
    return (amp * np.where(e % 2 == 0, 1.0, -1.0)).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    sc = cfg["rope_scaling"]
    m = 0.1 * float(sc["mscale_all_dim"]) * math.log(float(sc["factor"])) + 1
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """[rope / 2] float32: ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask`` as published."""
    d, base, sc = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), \
        cfg["rope_scaling"]
    factor, orig = float(sc["factor"]), \
        float(sc["original_max_position_embeddings"])
    freq = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp            # ``inv_freq_mask``
    return (freq / factor * (1 - extrapolated)
            + freq * extrapolated).astype(np.float32)


# ---------------------------------------------------------------- weights
def layer_shapes(cfg: dict, i: int) -> dict:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    out = {"ln_attn": (e,), "ln_mlp": (e,), "w_qa": (e, qr), "q_norm": (qr,),
           "w_qb": (qr, h * (nope + rope)), "w_kva": (e, kr + rope),
           "kv_norm": (kr,), "w_kvb": (kr, h * (nope + vd)),
           "wo": (h * vd, e)}
    if is_dense(cfg, i):
        m = cfg["intermediate_size"]
        return {**out, "w_gate": (e, m), "w_up": (e, m), "w_down": (m, e)}
    m, n = cfg["moe_intermediate_size"], len(held(cfg))
    ms = m * cfg["n_shared_experts"]
    return {**out, "shared_gate": (e, ms), "shared_up": (e, ms),
            "shared_down": (ms, e), "experts_gate": (n, e, m),
            "experts_up": (n, e, m), "experts_down": (n, m, e)}


@partial(jax.jit, static_argnames=("shape", "std"))
def _draw_router(key, *, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def make_layer(seed: int, i: int, cfg: dict) -> dict:
    shapes = tuple(sorted(layer_shapes(cfg, i).items()))
    key = jax.random.fold_in(weights.root_key(seed), i + 1)
    w = weights._draw_all(key, shapes, cfg["initializer_range"],
                          weights._dtype(cfg))
    if not is_dense(cfg, i):
        w["w_router"] = _draw_router(
            jax.random.fold_in(key, 0x6A7E),
            shape=(cfg["hidden_size"], router_experts(cfg)),
            std=cfg["initializer_range"])
    return w


make_top = weights.make_top          # embed, head, norm: as LLaMA's


# ---------------------------------------------------------------- forward
def _rope(x, inv):
    """x [S, ..., D] at positions 0..S-1: pair (i, i + D/2) by s * inv[i]."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q_nope, q_rope, k_nope, k_r, v, scale):
    """Causal MLA of one row, expanded, a block of queries at a time.
    q_nope, k_nope [S, H, nope]; q_rope [S, H, rope]; k_r [S, rope] (one
    key for all heads); v [S, H, vd] -> [S, H * vd]."""
    s, h, _ = q_nope.shape
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    pos = jnp.arange(s)

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * blk, blk, 0)
        at = i * blk + jnp.arange(blk)
        sc = (jnp.einsum("shd,thd->hst", qn, k_nope)
              + jnp.einsum("shd,td->hst", qr, k_r)) * scale
        sc = jnp.where(pos[None, None, :] <= at[None, :, None], sc, -jnp.inf)
        return jnp.einsum("hst,thd->shd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(s // blk))
    return out.reshape(s, h * v.shape[-1])


ROW_BLOCK = 2048        # rows a block of a whole row's MLP


def _swiglu(u, gate, up, down):
    """SwiGLU of [S, hidden]; a long row a block of rows at a time, so that
    [S, width] is never whole (18,432 wide at 18k tokens is 1.4 GB each for
    the gate, the up-projection and their product)."""
    one = lambda x: (jax.nn.silu(x @ gate) * (x @ up)) @ down
    s = u.shape[0]
    if s <= ROW_BLOCK or s % ROW_BLOCK:
        return one(u)
    return jax.lax.map(one, u.reshape(s // ROW_BLOCK, ROW_BLOCK, -1)) \
        .reshape(s, -1)


def route(u, w_router, bias, k, scaling):
    """-> (choice [S, k] expert ids, g [S, k] weights), float32."""
    s = jax.nn.sigmoid(u @ w_router)
    _, choice = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, choice, axis=1)
    return choice, scaling * picked / (picked.sum(-1, keepdims=True) + 1e-20)


def routed_sum(u, choice, g, ids, w, cap):
    """sum over the held experts ``ids`` of g_e Expert_e(u) for the tokens
    that chose e -> ([S, hidden], the most tokens any held expert got). An
    expert's tokens are gathered, ``cap`` at most."""
    s = u.shape[0]
    out, most = jnp.zeros_like(u), 0
    for j, e in enumerate(ids):
        weight = jnp.sum(jnp.where(choice == e, g, 0.0), axis=1)     # [S]
        chose = jnp.any(choice == e, axis=1)
        most = jnp.maximum(most, chose.sum())
        at = jnp.nonzero(chose, size=cap, fill_value=s)[0]
        rows = jnp.take(u, at, axis=0, mode="fill", fill_value=0.0)
        y = _swiglu(rows, w["experts_gate"][j], w["experts_up"][j],
                    w["experts_down"][j])
        y = y * jnp.take(weight, at, mode="fill", fill_value=0.0)[:, None]
        out = out.at[at].add(y, mode="drop")
    return out, most


@partial(jax.jit, static_argnames=("h", "nope", "rope", "vd", "rank", "eps",
                                   "scale", "k", "scaling", "ids", "cap"))
def layer(x, w, inv, bias, *, h, nope, rope, vd, rank, eps, scale, k,
          scaling, ids, cap):
    """One decoder layer on one row. x [S, hidden] float32 -> (x, held-
    expert bitmask of each token's choice [S] int32 (0 in a dense layer),
    the most tokens a held expert got)."""
    with jax.default_matmul_precision("highest"):
        w = {n: v.astype(jnp.float32) for n, v in w.items()}
        s = x.shape[0]
        u = _rms(x, w["ln_attn"], eps)
        q = (_rms(u @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(
            s, h, nope + rope)
        kv = u @ w["w_kva"]
        c_kv = _rms(kv[:, :rank], w["kv_norm"], eps)
        k_r = _rope(kv[:, rank:], inv)
        kvb = (c_kv @ w["w_kvb"]).reshape(s, h, nope + vd)
        ctx = attend(q[..., :nope], _rope(q[..., nope:], inv),
                     kvb[..., :nope], k_r, kvb[..., nope:], scale)
        x = x + ctx @ w["wo"]
        u = _rms(x, w["ln_mlp"], eps)
        if "w_gate" in w:
            return (x + _swiglu(u, w["w_gate"], w["w_up"], w["w_down"]),
                    jnp.zeros((s,), jnp.int32), 0)
        choice, g = route(u, w["w_router"], bias, k, scaling)
        y, most = routed_sum(u, choice, g, ids, w, cap)
        y = y + _swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"])
        mask = sum(jnp.any(choice == e, axis=1).astype(jnp.int32) << j
                   for j, e in enumerate(ids))
        return x + y, mask, most


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm.astype(jnp.float32), eps) \
            @ w_head.astype(jnp.float32)


def forward(cfg: dict, rows, top: dict, layer_weights, keep=None,
            routes=False):
    """Logits float32 for each row of token ids (1-D int arrays whose
    lengths ``Q_BLOCK`` divides or that lie under it): [S, vocab], or
    [len(keep[k]), vocab] at the positions ``keep[k]`` alone. ``top`` holds
    ``embed``, ``norm`` and ``head``; ``layer_weights(i)`` returns layer i's
    tensors. Layers outside, rows inside: a layer's weights are made once.
    With ``routes`` -> (logits, [a row's held-expert bitmasks, [expert
    layers, S or len(keep[k])] int32, bit j set where the token chose held
    expert ``held(cfg)[j]``])."""
    static = dict(h=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                  rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
                  rank=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"],
                  scale=softmax_scale(cfg), k=cfg["num_experts_per_tok"],
                  scaling=float(cfg["routed_scaling_factor"]), ids=held(cfg))
    inv, bias = jnp.asarray(yarn_inv_freq(cfg)), jnp.asarray(score_bias(cfg))
    xs = [jnp.take(top["embed"], jnp.asarray(r), axis=0).astype(jnp.float32)
          for r in rows]
    masks = [[] for _ in rows]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(i)
        for n, x in enumerate(xs):
            cap = expert_cap(cfg, len(x))
            out, mask, most = layer(x, w, inv, bias, cap=cap, **static)
            if not is_dense(cfg, i) and int(most) > cap:
                # an uneven row (an answer that repeats one token routes
                # alike): again, gathering as many as the fullest expert got
                cap = min(len(x), -(-int(most) // 256) * 256)
                out, mask, most = layer(x, w, inv, bias, cap=cap, **static)
            xs[n] = out
            if not is_dense(cfg, i):
                masks[n].append(mask if keep is None
                                else jnp.take(mask, jnp.asarray(keep[n])))
        del w
    if keep is not None:
        xs = [jnp.take(x, jnp.asarray(keep[n]), axis=0)
              for n, x in enumerate(xs)]
    logits = [head(x, top["norm"], top["head"], eps=cfg["rms_norm_eps"])
              for x in xs]
    return (logits, [jnp.stack(m) for m in masks]) if routes else logits
