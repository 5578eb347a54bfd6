"""Plain reference of the Olmo-Hybrid decoder (``model_type: olmo_hybrid``,
allenai Olmo-Hybrid-7B), written from the published description and
independent of ``paddle_tpu``. RMSNorm ``n(x; g) = x * rsqrt(mean(x^2) +
eps) * g``. Layer ``i`` is ``layer_types[i]``; both kinds are Olmo 2/3's
block, no norm before a branch and one after it:

    x = x + n(Mix_i(x); ln_attn_out)
    x = x + n((silu(x w_gate) * (x w_up)) w_down; ln_mlp_out)

``full_attention``: q = n(x wq; q_norm), k = n(x wk; k_norm) over the whole
projection, rotate-half RoPE (theta ``rope_theta``) on both, causal softmax
attention over ``num_attention_heads`` heads of ``head_dim``, times wo.

``linear_attention``: the gated delta rule, token by token (the recurrence,
NOT the chunked form the program's kernel computes). For token t and each
of the ``linear_num_key_heads`` heads (d_k, d_v the linear head dims):

    u_t   = x_t [wq | wk | wv]
    c_t   = silu(sum_{i=0..K-1} conv_w[i] * u_{t-K+1+i})     depthwise, causal
    q, k, v = split(c_t) a head;  q^ = q/|q| * d_k^-1/2;  k^ = k/|k|
    beta_t  = 2 sigmoid(x_t wb)                (the 2: linear_allow_neg_eigval)
    alpha_t = exp(-exp(A_log) * softplus(x_t wa + dt_bias))
    S'    = alpha_t S_{t-1}                    S in R^{d_v x d_k}, S_0 = 0
    S_t   = S' + beta_t (v_t - S' k^_t) k^_t^T
    o_t   = S_t q^_t
    y_t   = [n_head(o_t; o_norm) * silu(x_t wz)] wo

float32 throughout under ``jax.default_matmul_precision("highest")``; one
row at a time over its full sequence, no cache, no batching, no kernels; a
layer's weights come from a callable, are used by every row and dropped.
Attention is computed a block of queries at a time and logits at the kept
positions alone, so that a 16k-token row fits beside the weights.

Departures from the published description, each listed under ``assumed``
in the configuration's file because the catalog's row does not give it:
  * ``|q|`` is ``sqrt(sum q^2 + 1e-6)`` (the public kernels' l2norm);
  * ``head_dim`` 128, ``rope_theta`` 500,000 on the full layers, no
    position signal on the linear ones, no biases;
  * weights are random from the seed: ``initializer_range`` 0.02 for every
    matrix, norm gains near one, the convolution uniform on (-1/2, 1/2)
    (PyTorch's Conv1d default at fan-in 4), ``A`` uniform on (0, 16) and
    ``dt`` log-uniform on (1e-3, 1e-1) as the public Gated DeltaNet draws
    them (``dt_bias`` is the inverse softplus of ``dt``).

The tensors' names and how they are drawn are here too (``layer_shapes``,
``make_layer``, ``make_top``): through ``weights._draw_all`` and
``weights.root_key``, so a tensor stays a pure function of (seed, layer,
name).
"""
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference import _rms, _rope

LINEAR = "linear_attention"
Q_BLOCK = 2048          # queries a block of the reference's attention


# ------------------------------------------------------------------ weights
def kind(cfg: dict, i: int) -> str:
    return cfg["layer_types"][i]


def layer_shapes(cfg: dict, i: int) -> dict:
    e, m = cfg["hidden_size"], cfg["intermediate_size"]
    mlp = {"w_gate": (e, m), "w_up": (e, m), "w_down": (m, e),
           "ln_attn_out": (e,), "ln_mlp_out": (e,)}
    if kind(cfg, i) == LINEAR:
        h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                     cfg["linear_value_head_dim"])
        return {**mlp, "wq": (e, h * dk), "wk": (e, h * dk),
                "wv": (e, h * dv), "wz": (e, h * dv), "wb": (e, h),
                "wa": (e, h), "wo": (h * dv, e), "o_norm": (dv,)}
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return {**mlp, "wq": (e, nh * d), "wk": (e, nkv * d), "wv": (e, nkv * d),
            "wo": (nh * d, e), "q_norm": (nh * d,), "k_norm": (nkv * d,)}


@partial(jax.jit, static_argnames=("h", "chans", "taps", "dtype"))
def _draw_linear_extras(key, *, h, chans, taps, dtype):
    kc, ka, kd = jax.random.split(key, 3)
    conv = jax.random.uniform(kc, (taps, chans), jnp.float32, -0.5, 0.5)
    a = jax.random.uniform(ka, (h,), jnp.float32, 1e-3, 16.0)
    dt = jnp.exp(jax.random.uniform(kd, (h,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    # float32 both: a decay is exp(-A softplus(.)), and bfloat16 of A would
    # move it by percents
    return {"conv_w": conv.astype(dtype), "A_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}


def make_layer(seed: int, i: int, cfg: dict) -> dict:
    shapes = tuple(sorted(layer_shapes(cfg, i).items()))
    key = jax.random.fold_in(weights.root_key(seed), i + 1)
    w = weights._draw_all(key, shapes, cfg["initializer_range"],
                          weights._dtype(cfg))
    if kind(cfg, i) == LINEAR:
        h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                     cfg["linear_value_head_dim"])
        w.update(_draw_linear_extras(
            jax.random.fold_in(key, 0xDE17A), h=h, chans=h * (2 * dk + dv),
            taps=cfg["linear_conv_kernel_dim"], dtype=weights._dtype(cfg)))
    return w


make_top = weights.make_top          # embed, head, norm: as LLaMA's


# ------------------------------------------------------------------ forward
def _mlp(x, w, eps):
    m = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
    return x + _rms(m, w["ln_mlp_out"], eps)


def attend(q, k, v):
    """Causal attention of one row, a block of queries at a time. q, k, v:
    [S, H, D] -> [S, H * D]."""
    s, h, d = q.shape
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    pos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        at = i * blk + jnp.arange(blk)
        sc = jnp.einsum("shd,thd->hst", qb, k) / jnp.sqrt(jnp.float32(d))
        sc = jnp.where(pos[None, None, :] <= at[None, :, None], sc, -jnp.inf)
        return jnp.einsum("hst,thd->shd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(s // blk))        # [S/blk, blk, H, D]
    return out.reshape(s, h * d)


@partial(jax.jit, static_argnames=("nh", "nkv", "d", "eps", "theta"))
def full_layer(x, w, *, nh, nkv, d, eps, theta):
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        s = x.shape[0]
        q = _rope(_rms(x @ w["wq"], w["q_norm"], eps).reshape(s, nh, d), theta)
        k = _rope(_rms(x @ w["wk"], w["k_norm"], eps).reshape(s, nkv, d),
                  theta)
        v = (x @ w["wv"]).reshape(s, nkv, d)
        if nkv != nh:
            k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
        x = x + _rms(attend(q, k, v) @ w["wo"], w["ln_attn_out"], eps)
        return _mlp(x, w, eps)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, one token a step. q, k: [S, H, d_k] (normed); v: [S,
    H, d_v]; alpha, beta: [S, H] -> (o [S, H, d_v], the state after the
    last token [H, d_v, d_k])."""
    s, h, dk = q.shape

    def step(state, x):                          # state [H, d_v, d_k]
        qt, kt, vt, at, bt = x
        state = at[:, None, None] * state
        err = vt - jnp.einsum("hvk,hk->hv", state, kt)
        state = state + bt[:, None, None] * err[:, :, None] * kt[:, None, :]
        return state, jnp.einsum("hvk,hk->hv", state, qt)

    state, o = jax.lax.scan(
        step, jnp.zeros((h, v.shape[-1], dk), jnp.float32),
        (q, k, v, alpha, beta))
    return o, state


@partial(jax.jit, static_argnames=("h", "dk", "dv", "eps", "neg"))
def linear_layer(x, w, n, *, h, dk, dv, eps, neg):
    """-> (the layer's output [S, hidden], S_n [H, d_v, d_k]: the state the
    row's first ``n`` tokens leave; a token past them decays nothing and
    writes nothing, alpha 1 and beta 0, and what it reads is padding)."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        s = x.shape[0]
        u = x @ jnp.concatenate([w["wq"], w["wk"], w["wv"]], axis=1)
        taps = w["conv_w"].shape[0]
        ext = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u])
        c = jax.nn.silu(sum(ext[i:i + s] * w["conv_w"][i]
                            for i in range(taps)))
        q, k, v = jnp.split(c, [h * dk, 2 * h * dk], axis=1)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + 1e-6)
        q = unit(q.reshape(s, h, dk)) * dk ** -0.5
        k = unit(k.reshape(s, h, dk))
        beta = jax.nn.sigmoid(x @ w["wb"]) * (2.0 if neg else 1.0)
        alpha = jnp.exp(-jnp.exp(w["A_log"])
                        * jax.nn.softplus(x @ w["wa"] + w["dt_bias"]))
        live = (jnp.arange(s) < n)[:, None]
        o, state = delta_rule(q, k, v.reshape(s, h, dv),
                              jnp.where(live, alpha, 1.0),
                              jnp.where(live, beta, 0.0))
        o = _rms(o, w["o_norm"], eps) * jax.nn.silu(
            (x @ w["wz"]).reshape(s, h, dv))
        x = x + _rms(o.reshape(s, h * dv) @ w["wo"], w["ln_attn_out"], eps)
        return _mlp(x, w, eps), state


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm.astype(jnp.float32), eps) \
            @ w_head.astype(jnp.float32)


def forward(cfg: dict, rows, top: dict, layer_weights, keep=None,
            state_at=None):
    """Logits float32 for each row of token ids (1-D int arrays, of any
    lengths that ``Q_BLOCK`` divides or that lie under it): [S, vocab], or
    [len(keep[k]), vocab] at the positions ``keep[k]`` alone. ``top`` holds
    ``embed``, ``norm`` and ``head``; ``layer_weights(i)`` returns layer i's
    tensors. Layers outside, rows inside: a layer's weights are made once.
    With ``state_at`` ({row: n}) -> (logits, {row: [a linear layer's state
    after the row's first n tokens, [H, d_v, d_k] float32, ...]})."""
    eps = cfg["rms_norm_eps"]
    full = dict(nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
                d=cfg["head_dim"], eps=eps, theta=cfg["rope_theta"])
    lin = dict(h=cfg["linear_num_key_heads"], dk=cfg["linear_key_head_dim"],
               dv=cfg["linear_value_head_dim"], eps=eps,
               neg=bool(cfg["linear_allow_neg_eigval"]))
    xs = [jnp.take(top["embed"], jnp.asarray(r), axis=0).astype(jnp.float32)
          for r in rows]
    state_at = state_at or {}
    states = {k: [] for k in state_at}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(i)
        if kind(cfg, i) == LINEAR:
            for k, x in enumerate(xs):
                xs[k], s_n = linear_layer(x, w, state_at.get(k, len(x)),
                                          **lin)
                if k in states:
                    states[k].append(s_n)
        else:
            xs = [full_layer(x, w, **full) for x in xs]
        del w
    if keep is not None:
        xs = [jnp.take(x, jnp.asarray(keep[k]), axis=0)
              for k, x in enumerate(xs)]
    logits = [head(x, top["norm"], top["head"], eps=eps) for x in xs]
    return (logits, states) if states else logits
