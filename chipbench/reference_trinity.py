"""Plain reference of the Trinity decoder (``model_type: afmoe``, arcee-ai
Trinity-Mini), written from the catalog row's keys and the family's public
description, and independent of ``paddle_tpu``. RMSNorm ``n(x; g) = x *
rsqrt(mean(x^2) + eps) * g``, no biases, four norms a block (sandwich):

    h = E[token] * sqrt(hidden)                              (mup_enabled)
    h = h + n(Attn(n(h; ln1)); ln2);   h = h + n(F(n(h; ln3)); ln4)

**Attn**, for a row's normed input ``u`` [S, hidden], H heads of
``head_dim`` (which is not hidden / H), H_kv K/V heads:

    q = u wq, k = u wk, v = u wv, g = u wg          (g is H x head_dim wide)
    q = n(q_head; q_norm), k = n(k_head; k_norm)    over each head's dims
    window layer: q, k = rope(q), rope(k)   (theta, the whole head, dim i
                  paired with dim i + head_dim / 2); t attends s <= t with
                  t - s < sliding_window (the key itself counted)
    full layer:   NO positional encoding; t attends every s <= t
    o = softmax(q k^T * head_dim^-0.5) v;   o = o * sigmoid(g);   y = o wo

``layer_types`` names each layer's kind. The mask is built from positions.

**F**: a SwiGLU MLP of ``intermediate_size`` in the first
``num_dense_layers`` layers, the expert layer after them:

    s = sigmoid(u w_router)               float32, all ``num_experts``
    choice = top-k(s + b)                 b: the expert bias (a buffer)
    g_e = route_scale * s_e / (sum_{e in choice} s_e + 1e-20)   (route_norm)
    F(u) = Shared(u) + sum_{e in choice} g_e Expert_e(u)

Departures from the published code, each listed in the configuration's
``assumed``: the rotation's pairing of dims (halves, as they lie) and the
window's edge (``t - s < window``) are taken as the program's kernels
compute them; the router's product and sigmoid are float32.

float32 throughout under ``jax.default_matmul_precision("highest")``; one
row at a time over its full sequence, attention a block of queries at a
time and logits at the kept positions alone, so that a 17k-token row fits.
An expert is computed over the tokens routed to it, gathered up to a bound
(``expert_cap``: ``EXPERT_ROWS`` times the share of a row an even router
sends one expert; where an expert got more, ``forward`` runs the layer
again with the bound the fullest expert needs), one expert at a time under
a ``lax.scan`` over the stacked experts.

Weights and their names are here too (``layer_shapes``, ``make_layer``,
``make_top``), through ``weights._draw_all``: a tensor is a pure function
of (seed, layer, name). The router's matrix is float32; the expert bias is
NOT drawn: ``score_bias`` is a formula from the file, the same under every
seed.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import _rms

Q_BLOCK = 256           # queries a block of the reference's attention
EXPERT_ROWS = 3         # an expert computes this many times its even share
ROW_BLOCK = 2048        # rows a block of a whole row's MLP
WINDOW, FULL = "sliding_attention", "full_attention"


# ------------------------------------------------------------------ sizes
def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["num_dense_layers"]


def window_of(cfg: dict, i: int):
    """Layer i's window: ``sliding_window`` for a window layer, None for a
    full one."""
    return cfg["sliding_window"] if cfg["layer_types"][i] == WINDOW else None


def expert_cap(cfg: dict, s: int) -> int:
    """Rows an expert's gather holds for a row of ``s`` tokens."""
    even = s * cfg["num_experts_per_tok"] / cfg["num_experts"]
    return int(min(s, max(8, math.ceil(EXPERT_ROWS * even))))


def score_bias(cfg: dict) -> np.ndarray:
    """The expert bias: ``amplitude * (-1)^e``, float32."""
    amp = float(cfg["expert_bias"]["amplitude"])
    e = np.arange(cfg["num_experts"])
    return (amp * np.where(e % 2 == 0, 1.0, -1.0)).astype(np.float32)


def inv_freq(cfg: dict) -> np.ndarray:
    d = cfg["head_dim"]
    return (1.0 / float(cfg["rope_theta"])
            ** (np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32)


# ---------------------------------------------------------------- weights
def layer_shapes(cfg: dict, i: int) -> dict:
    e, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"ln1": (e,), "ln2": (e,), "ln3": (e,), "ln4": (e,),
           "wq": (e, nh * d), "wk": (e, nkv * d), "wv": (e, nkv * d),
           "wg": (e, nh * d), "wo": (nh * d, e), "q_norm": (d,),
           "k_norm": (d,)}
    if is_dense(cfg, i):
        m = cfg["intermediate_size"]
        return {**out, "w_gate": (e, m), "w_up": (e, m), "w_down": (m, e)}
    m, n = cfg["moe_intermediate_size"], cfg["num_experts"]
    ms = m * cfg["num_shared_experts"]
    return {**out, "shared_gate": (e, ms), "shared_up": (e, ms),
            "shared_down": (ms, e), "experts_gate": (n, e, m),
            "experts_up": (n, e, m), "experts_down": (n, m, e)}


@partial(jax.jit, static_argnames=("shape", "std"))
def _draw_router(key, *, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def make_layer(seed: int, i: int, cfg: dict) -> dict:
    """Layer i's tensors. The expert stacks are drawn a call each (805 M
    values a layer at the published widths: drawn together, their float32
    normals would be 3 GB of temporaries beside a model that fills the
    chip), everything else in one."""
    shapes = layer_shapes(cfg, i)
    key = jax.random.fold_in(weights.root_key(seed), i + 1)
    draw = lambda k, names: weights._draw_all(  # noqa: E731
        k, tuple(sorted((n, shapes[n]) for n in names)),
        cfg["initializer_range"], weights._dtype(cfg))
    stacks = sorted(n for n in shapes if n.startswith("experts_"))
    w = draw(key, set(shapes) - set(stacks))
    for j, name in enumerate(stacks):
        w.update(draw(jax.random.fold_in(key, 0xE0 + j), [name]))
    if not is_dense(cfg, i):
        w["w_router"] = _draw_router(
            jax.random.fold_in(key, 0x6A7E),
            shape=(cfg["hidden_size"], cfg["num_experts"]),
            std=cfg["initializer_range"])
    return w


make_top = weights.make_top          # embed, head, norm: as LLaMA's


# ---------------------------------------------------------------- forward
def _rope(x, inv):
    """x [S, H, D] at positions 0..S-1: pair (i, i + D/2) by s * inv[i]."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv[None, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, window):
    """Causal attention of one row, a block of queries at a time. q [S, H,
    D]; k, v [S, H_kv, D] (query head h reads K/V head h // (H / H_kv));
    ``window`` None or the positions a query reads, itself counted -> [S,
    H * D]."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    pos = jnp.arange(s)
    qg = q.reshape(s, h // rep, rep, d)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk, 0)
        at = i * blk + jnp.arange(blk)
        sc = jnp.einsum("sgrd,tgd->grst", qb, k) * d ** -0.5
        keep = pos[None, :] <= at[:, None]
        if window is not None:
            keep &= at[:, None] - pos[None, :] < window
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        return jnp.einsum("grst,tgd->sgrd", jax.nn.softmax(sc, axis=-1), v)

    return jax.lax.map(block, jnp.arange(s // blk)).reshape(s, h * d)


def _swiglu(u, gate, up, down):
    """SwiGLU of [S, hidden]; a long row a block of rows at a time."""
    one = lambda x: (jax.nn.silu(x @ gate) * (x @ up)) @ down
    s = u.shape[0]
    if s <= ROW_BLOCK or s % ROW_BLOCK:
        return one(u)
    return jax.lax.map(one, u.reshape(s // ROW_BLOCK, ROW_BLOCK, -1)) \
        .reshape(s, -1)


def route(u, w_router, bias, k, scaling):
    """-> (choice [S, k] expert ids, g [S, k] weights), float32: chosen by
    the biased score, weighted by the unbiased."""
    s = jax.nn.sigmoid(u @ w_router)
    _, choice = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, choice, axis=1)
    return choice, scaling * picked / (picked.sum(-1, keepdims=True) + 1e-20)


def routed_sum(u, choice, g, w, cap):
    """sum over every expert e of g_e Expert_e(u) for the tokens that chose
    e -> ([S, hidden], the most tokens any expert got). An expert's tokens
    are gathered, ``cap`` at most."""
    s = u.shape[0]

    def one(carry, ex):
        out, most = carry
        e, gate, up, down = ex
        weight = jnp.sum(jnp.where(choice == e, g, 0.0), axis=1)     # [S]
        chose = jnp.any(choice == e, axis=1)
        at = jnp.nonzero(chose, size=cap, fill_value=s)[0]
        rows = jnp.take(u, at, axis=0, mode="fill", fill_value=0.0)
        y = (jax.nn.silu(rows @ gate) * (rows @ up)) @ down
        y = y * jnp.take(weight, at, mode="fill", fill_value=0.0)[:, None]
        return (out.at[at].add(y, mode="drop"),
                jnp.maximum(most, chose.sum())), None

    n = w["experts_gate"].shape[0]
    (out, most), _ = jax.lax.scan(
        one, (jnp.zeros_like(u), jnp.int32(0)),
        (jnp.arange(n), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    return out, most


@partial(jax.jit, static_argnames=("nh", "nkv", "d", "eps", "window",
                                   "rope", "k", "scaling", "cap"))
def layer(x, w, inv, bias, *, nh, nkv, d, eps, window, rope, k, scaling,
          cap):
    """One decoder layer on one row. x [S, hidden] float32 -> (x, the most
    tokens an expert got: 0 in a dense layer). ``rope``: whether q and k
    are rotated; ``window``: None or the layer's window."""
    with jax.default_matmul_precision("highest"):
        w = {n: v.astype(jnp.float32) for n, v in w.items()}
        s = x.shape[0]
        u = _rms(x, w["ln1"], eps)
        q = _rms((u @ w["wq"]).reshape(s, nh, d), w["q_norm"], eps)
        kk = _rms((u @ w["wk"]).reshape(s, nkv, d), w["k_norm"], eps)
        v = (u @ w["wv"]).reshape(s, nkv, d)
        if rope:
            q, kk = _rope(q, inv), _rope(kk, inv)
        o = attend(q, kk, v, window) * jax.nn.sigmoid(u @ w["wg"])
        x = x + _rms(o @ w["wo"], w["ln2"], eps)
        u = _rms(x, w["ln3"], eps)
        if "w_gate" in w:
            y, most = _swiglu(u, w["w_gate"], w["w_up"], w["w_down"]), 0
        else:
            choice, g = route(u, w["w_router"], bias, k, scaling)
            y, most = routed_sum(u, choice, g, w, cap)
            y = y + _swiglu(u, w["shared_gate"], w["shared_up"],
                            w["shared_down"])
        return x + _rms(y, w["ln4"], eps), most


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm.astype(jnp.float32), eps) \
            @ w_head.astype(jnp.float32)


def layer_static(cfg: dict, i: int) -> dict:
    """The static arguments of layer i: what a control changes to plant a
    fault (``control_trinity.py`` does not; the tests do)."""
    return dict(nh=cfg["num_attention_heads"],
                nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
                eps=cfg["rms_norm_eps"], window=window_of(cfg, i),
                rope=cfg["layer_types"][i] == WINDOW,
                k=cfg["num_experts_per_tok"],
                scaling=float(cfg["route_scale"]))


def forward(cfg: dict, rows, top: dict, layer_weights, keep=None,
            static=layer_static):
    """Logits float32 for each row of token ids (1-D int arrays whose
    lengths ``Q_BLOCK`` divides or that lie under it): [S, vocab], or
    [len(keep[k]), vocab] at the positions ``keep[k]`` alone. ``top`` holds
    ``embed``, ``norm`` and ``head``; ``layer_weights(i)`` returns layer i's
    tensors. Layers outside, rows inside: a layer's weights are made once."""
    inv, bias = jnp.asarray(inv_freq(cfg)), jnp.asarray(score_bias(cfg))
    scale = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0
    xs = [jnp.take(top["embed"], jnp.asarray(r), axis=0).astype(jnp.float32)
          * scale for r in rows]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(i)
        for n, x in enumerate(xs):
            cap = expert_cap(cfg, len(x))
            out, most = layer(x, w, inv, bias, cap=cap, **static(cfg, i))
            if int(most) > cap:
                # an uneven row (an answer that repeats one token routes
                # alike): again, gathering as many as the fullest expert got
                cap = min(len(x), -(-int(most) // 256) * 256)
                out, most = layer(x, w, inv, bias, cap=cap,
                                  **static(cfg, i))
            xs[n] = out
        del w
    if keep is not None:
        xs = [jnp.take(x, jnp.asarray(keep[n]), axis=0)
              for n, x in enumerate(xs)]
    return [head(x, top["norm"], top["head"], eps=cfg["rms_norm_eps"])
            for x in xs]
