"""The controls of a served Trinity cell, and the readings for the cell's
limits, taken on the chip in one process:

    python chipbench/control_trinity.py <cell> <seconds> <seed> ...
        [--control <what> <seed> ...] ...

First a sound run of the cell for every seed before ``--control``, then, for
each ``--control``, a run for every seed after it with ``<what>`` planted in
the program:

    weights=int8      the program serving weights that int8 holds: every
                      projection of attention (q, k, v, the gate, o), the
                      dense MLP, the shared expert, the experts and the head
                      rounded to int8 with one scale a column
                      (``control.round_to``), as weight-only quantisation
                      would hold them: the nearest precision below the
                      bfloat16 the configuration states. (The family is
                      refused an int8 cache, and ``serving/quant.py`` does
                      not walk expert stacks, so the rounding is done here,
                      on the built model. The router stays float32.)
    bias=dropped      a planted fault, not a precision: the program selects
                      by the unbiased score (``gate_bias`` zero): one
                      token-layer in ten then holds another set of experts
    window=all        a planted fault of the mechanism: the window layers
                      attend everything (the model built with a window of
                      2**20, so nothing is recycled either): rows longer
                      than the published window differ
    rope=global       likewise: the full layer's q and k are rotated, where
                      the model gives a full layer no positional encoding

Each run prints ``{"reading": "sound" | "control", "seed", "numbers", ...}``:
the numbers ``correct`` compares, beside the cell's limits. Used by hand
and by the tests, never by a run of the benchmark.
"""
import sys
import time
from contextlib import contextmanager
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

MATRICES = ("qkv_proj", "gate_proj", "o_proj", "gate_up_proj", "down_proj")


@contextmanager
def _built(change, **planted):
    """The builder's ``build`` with ``change(model)`` applied to what it
    returns, and ``planted`` among the program configuration's keys."""
    from chipbench.builders import trinity as builder
    build = builder.build

    def built(cfg, seed, **overrides):
        model = build(cfg, seed, **{**overrides, **planted})
        change(model)
        return model

    builder.build = built
    try:
        yield
    finally:
        builder.build = build


def weights_int8():
    import jax
    from chipbench import control
    rounded = jax.jit(lambda w: control.round_to(
        w.astype("float32"), "int8", axis=-2).astype(w.dtype))

    def change(model):
        model.lm_head = rounded(model.lm_head)
        for lyr in model.layers:
            parts = [lyr.self_attn] + ([lyr.mlp.shared] if lyr.sparse
                                       else [lyr.mlp])
            for part in parts:
                for name in MATRICES:
                    if getattr(part, name, None) is not None:
                        setattr(part, name, rounded(getattr(part, name)))
            if lyr.sparse:
                ex = lyr.mlp.moe.experts
                ex.gate_up, ex.down = rounded(ex.gate_up), rounded(ex.down)
    return _built(change)


def bias_dropped():
    def change(model):
        for lyr in model.layers:
            if lyr.sparse:
                lyr.mlp.moe.gate_bias = lyr.mlp.moe.gate_bias * 0
    return _built(change)


def window_all():
    return _built(lambda model: None, sliding_window=1 << 20)


def rope_global():
    def change(model):
        for lyr in model.layers:
            lyr.self_attn.use_rope = True
    return _built(change)


CONTROLS = {"weights=int8": weights_int8, "bias=dropped": bias_dropped,
            "window=all": window_all, "rope=global": rope_global}


def main(argv):
    from chipbench import correct_trinity, run
    cell, seconds, rest = argv[0], float(argv[1]), argv[2:]
    groups = [[]]
    for word in rest:
        if word == "--control":
            groups.append([])
        else:
            groups[-1].append(word)
    runs = [("sound", None, [int(x) for x in groups[0]])] + [
        ("control", g[0], [int(x) for x in g[1:]]) for g in groups[1:]]
    last = {}
    served = correct_trinity.served

    def noted(*a, **kw):
        last["verdict"] = served(*a, **kw)
        return last["verdict"]

    correct_trinity.served = noted
    try:
        for label, what, seeds in runs:
            for seed in seeds:
                last["verdict"] = {}
                with (CONTROLS[what]() if what else _nothing()):
                    try:
                        code, res = run.run_cell(
                            cell, seed, seconds, False,
                            t_start=time.perf_counter())
                    except Exception as e:  # a control that crashes failed
                        if label != "control":
                            raise
                        code, res = f"{type(e).__name__}: {e}"[:300], None
                v = last["verdict"]
                run.note(reading=label, seed=seed, code=code, planted=what,
                         numbers=v.get("numbers"),
                         tokens_compared=v.get("tokens_compared"),
                         run_correct=res and res["correct"],
                         metrics=res and res["metrics"],
                         memory_peak_bytes=res and res["device"][
                             "memory_peak_bytes"])
    finally:
        correct_trinity.served = served


@contextmanager
def _nothing():
    yield


if __name__ == "__main__":
    main(sys.argv[1:])
