"""Plain reference of the LLaMA-shaped decoder, written from the published
description and independent of ``paddle_tpu``: RMSNorm, rotary embedding
(rotate-half), grouped-query causal attention, SwiGLU, untied output head.

float32 throughout under ``jax.default_matmul_precision("highest")`` (a TPU
multiplies float32 in lower precision otherwise). Weights come one layer at
a time from a callable, are upcast on arrival, and are dropped before the
next layer, so a float32 copy of the whole model never exists. No kernels,
no cache, no batching: one row at a time over its full sequence, scores
materialised one K/V head at a time.
"""
from functools import partial

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [S, H, D]; position s rotates pair (i, i + D/2) by s / theta^(2i/D)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("nh", "nkv", "d", "eps", "theta"))
def layer(x, w, *, nh, nkv, d, eps, theta):
    """One decoder layer on one row. x: [S, hidden] float32; w: the layer's
    tensors as published ([in, out])."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        s = x.shape[0]
        hn = _rms(x, w["ln_attn"], eps)
        q = _rope((hn @ w["wq"]).reshape(s, nh, d), theta)
        k = _rope((hn @ w["wk"]).reshape(s, nkv, d), theta)
        v = (hn @ w["wv"]).reshape(s, nkv, d)
        ctx = attend(q, k, v)
        x = x + ctx @ w["wo"]
        hn = _rms(x, w["ln_mlp"], eps)
        x = x + (jax.nn.silu(hn @ w["w_gate"]) * (hn @ w["w_up"])) @ w["w_down"]
        return x


def attend(q, k, v):
    """Causal grouped-query attention of one row. q: [S, nh, D]; k, v:
    [S, nkv, D] -> [S, nh * D]. Scores of one K/V head and the query heads
    it serves at a time."""
    s, nh, d = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    qg = jnp.moveaxis(q.reshape(s, nkv, g, d), 1, 0)          # [nkv, S, g, D]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint            # backward recomputes the scores of a group
    def group(qkv):
        qn, kn, vn = qkv
        sc = jnp.einsum("sgd,td->gst", qn, kn) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", p, vn)

    ctx = jax.lax.map(group, (qg, jnp.moveaxis(k, 1, 0),
                              jnp.moveaxis(v, 1, 0)))         # [nkv, S, g, D]
    return jnp.moveaxis(ctx, 0, 1).reshape(s, nh * d)


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm.astype(jnp.float32), eps) @ w_head.astype(jnp.float32)


def forward(cfg: dict, rows, top: dict, layer_weights, keep=None):
    """Logits float32 for each row of token ids (1-D int arrays, of any
    lengths): [S, vocab], or [len(keep[k]), vocab] at the positions
    ``keep[k]`` alone. ``top`` holds ``embed``, ``norm``, ``head``;
    ``layer_weights(i)`` returns layer i's tensors. Layers outside, rows
    inside, so each layer's weights are made once."""
    kw = dict(nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
              d=cfg["head_dim"], eps=cfg["rms_norm_eps"],
              theta=cfg["rope_theta"])
    xs = [jnp.take(top["embed"], jnp.asarray(r), axis=0).astype(jnp.float32)
          for r in rows]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(i)
        xs = [layer(x, w, **kw) for x in xs]
        del w
    if keep is not None:
        xs = [jnp.take(x, jnp.asarray(at), axis=0) for x, at in zip(xs, keep)]
    return [head(x, top["norm"], top["head"], eps=cfg["rms_norm_eps"])
            for x in xs]


# ------------------------------------------------------------------ training
@partial(jax.jit, static_argnames=("eps",))
def head_loss_vjp(x, norm, w_head, labels, scale, *, eps):
    """One row's summed cross-entropy over labels >= 0, and ``scale`` times
    its gradient with respect to (x, norm, w_head)."""
    def f(x, norm, w_head):
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(_rms(x, norm, eps) @ w_head, axis=-1)
        tok = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], 1)
        return jnp.sum(jnp.where(labels >= 0, tok[:, 0], 0.0))
    loss, back = jax.vjp(f, x, norm, w_head)
    return loss, back(scale)


@partial(jax.jit, static_argnames=("nh", "nkv", "d", "eps", "theta"))
def layer_vjp(x, w, ct, **kw):
    """(d loss / d x, d loss / d w) of one layer on one row."""
    return jax.vjp(lambda x, w: layer(x, w, **kw), x, w)[1](ct)


@jax.jit
def _adamw(p, g, m, v, clip, t, lr, b1, b2, eps, wd):
    """AdamW as published (decoupled decay, bias-corrected moments), on the
    gradient already scaled by the global-norm clip."""
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p
    return p - lr * step, m, v, jnp.sqrt(jnp.sum(g * g))


_sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))
_dist = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))


def train(cfg, opt, batches, top, layer_weights, devices):
    """Plain float32 training: ``len(batches)`` AdamW steps from the seeded
    weights, every tensor and both its moments in float32, the loss the mean
    cross-entropy over labels >= 0, the gradient clipped to ``opt["clip"]``
    by its global norm. Layer i and its optimizer state live on
    ``devices[i % len(devices)]``, the tensors outside the layers on the
    first; activations move between them. One row at a time, one layer at a
    time, backward by ``jax.vjp`` of the forward.

    -> {"loss": [per step], "grad_norm": {leaf: norm of the first clipped
    gradient}, "delta_norm": {leaf: norm of the change after all steps}},
    leaves named ``L<i>.<tensor>``, ``embed``, ``norm``, ``head``."""
    kw = dict(nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
              d=cfg["head_dim"], eps=cfg["rms_norm_eps"],
              theta=cfg["rope_theta"])
    eps, depth = cfg["rms_norm_eps"], cfg["num_hidden_layers"]
    dev = lambda i: devices[i % len(devices)]
    f32 = lambda tree, d: {k: jax.device_put(v, d).astype(jnp.float32)
                           for k, v in tree.items()}
    zeros = lambda tree: {k: jnp.zeros_like(v) for k, v in tree.items()}
    params = [f32(layer_weights(i), dev(i)) for i in range(depth)]
    params.append(f32(top, devices[0]))               # index -1: the top
    m1, m2 = [zeros(p) for p in params], [zeros(p) for p in params]
    out = {"loss": [], "grad_norm": {}, "delta_norm": {}}
    name = lambda i, k: k if i in (-1, depth) else f"L{i}.{k}"

    for t, (ids, labels) in enumerate(batches, start=1):
        count = float((labels >= 0).sum())
        tp = params[-1]
        xs = [jnp.take(tp["embed"], jnp.asarray(r), axis=0) for r in ids]
        acts = []
        for i in range(depth):
            xs = [jax.device_put(x, dev(i)) for x in xs]
            acts.append(xs)
            xs = [layer(x, params[i], **kw) for x in xs]
        grads = [None] * depth + [zeros(tp)]
        loss, dxs = 0.0, []
        for x, lab in zip(xs, labels):
            l, (dx, dn, dh) = head_loss_vjp(
                jax.device_put(x, devices[0]), tp["norm"], tp["head"],
                jnp.asarray(lab), jnp.float32(1.0 / count), eps=eps)
            loss += float(l) / count
            grads[-1]["norm"] += dn
            grads[-1]["head"] += dh
            dxs.append(dx)
        for i in reversed(range(depth)):
            acc = zeros(params[i])
            for r, x in enumerate(acts[i]):
                dxs[r], dw = layer_vjp(x, params[i],
                                       jax.device_put(dxs[r], dev(i)), **kw)
                acc = {k: acc[k] + dw[k] for k in acc}
            grads[i], acts[i] = acc, None
        for r, dx in zip(ids, dxs):
            grads[-1]["embed"] = grads[-1]["embed"].at[jnp.asarray(r)].add(
                jax.device_put(dx, devices[0]))
        gnorm = float(sum(float(_sq(g)) for tree in grads
                          for g in tree.values())) ** 0.5
        clip = min(1.0, opt["clip"] / max(gnorm, 1e-12))
        for i, (p, g) in enumerate(zip(params, grads)):
            for k in p:
                p[k], m1[i][k], m2[i][k], gn = _adamw(
                    p[k], g[k], m1[i][k], m2[i][k], clip, float(t), opt["lr"],
                    opt["beta1"], opt["beta2"], opt["eps"],
                    opt["weight_decay"])
                if t == 1:
                    out["grad_norm"][name(i, k)] = float(gn)
        out["loss"].append(loss)
        del grads
    for i, p in enumerate(params):
        p0 = f32(layer_weights(i), dev(i)) if i < depth else f32(top, devices[0])
        for k in p:
            out["delta_norm"][name(i, k)] = float(_dist(p[k], p0[k]))
    return out
