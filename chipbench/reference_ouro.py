"""Plain reference of the Ouro looped decoder (``model_type: ouro``,
ByteDance Ouro-1.4B / 2.6B), written from the published description and
independent of ``paddle_tpu``. RMSNorm ``n(x; g) = x * rsqrt(mean(x^2) +
eps) * g``; ``L`` layers, ``U = total_ut_steps`` passes:

    layer l, pass u:  a = Attn_l(n(x; ln_attn))        rotate-half RoPE on q, k; causal
                      x = x + n(a; ln_attn_out)        norm of the branch, then the add
                      h = n(x; ln_mlp)
                      m = (silu(h w_gate) * (h w_up)) w_down
                      x = x + n(m; ln_mlp_out)
    pass u:           x_0^u = x^{u-1} (x^0 the embedding); layers 0..L-1;
                      x^u = n(x_L^u; norm)             the next pass starts from x^u
                      gate_u = x^u exit_w + exit_b;  logits_u = x^u head
    exit:             lam_u = sigmoid(gate_u); p_u = lam_u prod_{v<u}(1 - lam_v)
                      for u < U, p_U the remainder; a token leaves at the first u
                      whose cumulative p reaches early_exit_threshold, else at U

The weights are the same in every pass. float32 throughout under
``jax.default_matmul_precision("highest")``; one row at a time over its
full sequence, no cache, no kernels; a layer's weights come from a
callable, are used by every row and dropped.

Departures from the published file (``modeling_ouro.py``), each because
the catalog's row does not give it; the configuration lists them under
``assumed``:
  * no bias on q/k/v/o and the MLP; the gate is hidden -> 1 with a bias;
  * at ``early_exit_threshold`` >= 1 no token leaves early, whatever a
    saturated sigmoid would say: the logits are the last pass's;
  * weights are random from the seed (``initializer_range`` 0.02).

The tensors' names and how they are drawn are here too (``layer_shapes``,
``make_layer``, ``make_top``): through ``weights._draw_all`` and
``weights.root_key``, so a tensor stays a pure function of (seed, layer,
name), as for the LLaMA-shaped models.
"""
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference import _rms, _rope, attend


# ------------------------------------------------------------------ weights
def layer_shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {**weights.layer_shapes(cfg), "ln_attn_out": (h,),
            "ln_mlp_out": (h,)}


def make_layer(seed: int, i: int, cfg: dict) -> dict:
    shapes = tuple(sorted(layer_shapes(cfg).items()))
    return weights._draw_all(
        jax.random.fold_in(weights.root_key(seed), i + 1), shapes,
        cfg["initializer_range"], weights._dtype(cfg))


def make_top(seed: int, cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = (("embed", (v, h)), ("exit_b", (1,)), ("exit_w", (h, 1)),
              ("head", (h, v)), ("norm", (h,)))
    top = weights._draw_all(jax.random.fold_in(weights.root_key(seed), 0),
                            shapes, cfg["initializer_range"],
                            weights._dtype(cfg))
    # ``_draw_all`` draws every 1-D tensor as a norm gain (near one); the
    # gate's bias starts at its drawn value less one: near zero
    top["exit_b"] = top["exit_b"] - 1
    return top


# ------------------------------------------------------------------ forward
@partial(jax.jit, static_argnames=("nh", "nkv", "d", "eps", "theta"))
def layer(x, w, *, nh, nkv, d, eps, theta):
    """One sandwich layer on one row. x: [S, hidden] float32; w: the
    layer's tensors as published ([in, out])."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        s = x.shape[0]
        hn = _rms(x, w["ln_attn"], eps)
        q = _rope((hn @ w["wq"]).reshape(s, nh, d), theta)
        k = _rope((hn @ w["wk"]).reshape(s, nkv, d), theta)
        v = (hn @ w["wv"]).reshape(s, nkv, d)
        x = x + _rms(attend(q, k, v) @ w["wo"], w["ln_attn_out"], eps)
        hn = _rms(x, w["ln_mlp"], eps)
        m = (jax.nn.silu(hn @ w["w_gate"]) * (hn @ w["w_up"])) @ w["w_down"]
        return x + _rms(m, w["ln_mlp_out"], eps)


@partial(jax.jit, static_argnames=("eps",))
def end_of_pass(x, norm, exit_w, exit_b, *, eps):
    """-> (x^u, gate_u [S]): the final norm, and the exit gate on it."""
    with jax.default_matmul_precision("highest"):
        x = _rms(x, norm.astype(jnp.float32), eps)
        gate = x @ exit_w.astype(jnp.float32) + exit_b.astype(jnp.float32)
        return x, gate[:, 0]


@jax.jit
def project(x, w_head):
    with jax.default_matmul_precision("highest"):
        return x @ w_head.astype(jnp.float32)


def exit_pass(gates, threshold):
    """gates [U, S] -> the pass (0-based) at which each position leaves."""
    lam = jax.nn.sigmoid(gates)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = (lam * before).at[-1].set(before[-1])
    reached = (jnp.cumsum(p, axis=0) >= threshold).at[-1].set(True)
    return jnp.argmax(reached, axis=0)


def forward(cfg: dict, rows, top: dict, layer_weights, keep=None,
            with_gates=False):
    """Logits float32 for each row of token ids (1-D int arrays, of any
    lengths): [S, vocab], or [len(keep[k]), vocab] at the positions
    ``keep[k]`` alone; with ``with_gates`` a list of (logits, gates [U, S
    or len(keep[k])]). ``top`` holds ``embed``, ``norm``, ``head``,
    ``exit_w``, ``exit_b``; ``layer_weights(i)`` returns layer i's
    tensors. Passes outside, layers inside them, rows innermost: a layer's
    weights are made once a pass."""
    kw = dict(nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
              d=cfg["head_dim"], eps=cfg["rms_norm_eps"],
              theta=cfg["rope_theta"])
    passes, threshold = cfg["total_ut_steps"], cfg["early_exit_threshold"]
    xs = [jnp.take(top["embed"], jnp.asarray(r), axis=0).astype(jnp.float32)
          for r in rows]
    states, gates = [], []
    for _ in range(passes):
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(i)
            xs = [layer(x, w, **kw) for x in xs]
            del w
        ended = [end_of_pass(x, top["norm"], top["exit_w"], top["exit_b"],
                             eps=cfg["rms_norm_eps"]) for x in xs]
        xs = [x for x, _ in ended]
        at = (lambda v, k: v if keep is None
              else jnp.take(v, jnp.asarray(keep[k]), axis=0))
        gates.append([at(g, k) for k, (_, g) in enumerate(ended)])
        if threshold < 1 or len(gates) == passes:   # states that can be left at
            states.append([at(x, k) for k, x in enumerate(xs)])
    out = []
    for k in range(len(rows)):
        g = jnp.stack([gu[k] for gu in gates])
        x = states[-1][k]
        if threshold < 1:
            leave = exit_pass(g, threshold)
            x = jnp.take_along_axis(jnp.stack([s[k] for s in states]),
                                    leave[None, :, None], axis=0)[0]
        logits = project(x, top["head"])
        out.append((logits, g) if with_gates else logits)
    return out
