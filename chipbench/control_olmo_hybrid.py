"""The controls of a served Olmo-Hybrid cell that ``control.py`` cannot
express, and the readings for the cell's limits, taken on the chip in one
process:

    python chipbench/control_olmo_hybrid.py <cell> <seconds> <seed> ...
        [--control <what> <seed> ...] ...

First a sound run of the cell for every seed before ``--control``, then, for
each ``--control``, a run for every seed after it with ``<what>`` planted
in the program:

    state=bfloat16        the program with the recurrent state kept in
                          bfloat16, the nearest precision below the float32
                          the configuration states for it. Every state a
                          call hands back (a prefill call's, a chunk's, a
                          decode tick's) is rounded, as a store of that type
                          would hold it: what ``state_gap`` is there to
                          refuse
    weights=int8          the program serving weights that int8 holds: every
                          projection, the MLP and the head rounded to int8
                          with one scale a column (``control.round_to``), as
                          weight-only quantisation would hold them: the
                          nearest precision below the bfloat16 the
                          configuration states. (``serving/quant.py`` walks
                          attention layers only, so the rounding is done
                          here, on the built model.)
    hit=past_snapshot     a planted fault, not a precision: a snapshot is
                          taken a chunk (or, in a prefix shorter than that,
                          all but a block) before the depth its trie
                          position claims, so a later hit adopts K/V past
                          the state it restores

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


@contextmanager
def state_kept_in(dtype):
    """``lax.reduce_precision``, not a cast there and back: XLA on the TPU
    removes a float32 -> bfloat16 -> float32 pair of converts
    (``xla_allow_excess_precision``, on by default), so a control planted
    as two casts ran as the sound program there (PR 35's first bfloat16 and
    float8 controls did: they read inside the sound range on the chip and
    far outside it on the CPU)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import paged
    from paddle_tpu.models.olmo_hybrid import GatedDeltaMixer
    mix = GatedDeltaMixer.mix
    info = jnp.finfo(jnp.dtype(dtype))

    def rounded(self, x, state, conv, lens):
        y, state, conv = mix(self, x, state, conv, lens)
        return y, jax.lax.reduce_precision(state, info.nexp, info.nmant), conv

    GatedDeltaMixer.mix = rounded
    paged.clear_jit_caches()
    try:
        yield
    finally:
        GatedDeltaMixer.mix = mix
        paged.clear_jit_caches()


MATRICES = ("qkv_proj", "o_proj", "qkvz_proj", "ba_proj", "gate_up_proj",
            "down_proj")


@contextmanager
def weights_int8():
    import jax
    from chipbench import control
    from chipbench.builders import olmo_hybrid as builder
    build = builder.build
    rounded = jax.jit(lambda w: control.round_to(
        w.astype("float32"), "int8", axis=0).astype(w.dtype))

    def built(cfg, seed, **overrides):
        model = build(cfg, seed, **overrides)
        model.lm_head = rounded(model.lm_head)
        for lyr in model.model.layers:
            for part in (getattr(lyr, "self_attn", None),
                         getattr(lyr, "linear_attn", None), lyr.mlp):
                for name in MATRICES:
                    if getattr(part, name, None) is not None:
                        setattr(part, name, rounded(getattr(part, name)))
        return model

    builder.build = built
    try:
        yield
    finally:
        builder.build = build


@contextmanager
def hit_past_snapshot():
    from paddle_tpu.serving.engine import LLMEngine
    from paddle_tpu.models.paged import RadixPrefixBlockManager as Mgr
    admit, attach = LLMEngine._admit_state, Mgr.attach_snapshot
    early = {}                           # entry -> tokens it was taken early

    def admit_early(self, req, slot, match):
        planned = admit(self, req, slot, match)
        plan = req._snapshot_plan
        restored = match.token_count if match else 0
        if plan is not None:
            # a chunk early, and never at or before what was restored
            shift = min(self.max_prompt_len,
                        plan[0] - restored - self.block_size)
            if shift > 0:
                early[plan[1]] = shift
                req._snapshot_plan = (plan[0] - shift, plan[1])
        return planned

    def attach_deeper(self, tokens, depth, idx, adapter=None):
        return attach(self, tokens, depth + early.pop(idx, 0), idx, adapter)

    LLMEngine._admit_state, Mgr.attach_snapshot = admit_early, attach_deeper
    try:
        yield
    finally:
        LLMEngine._admit_state, Mgr.attach_snapshot = admit, attach


CONTROLS = {"state=bfloat16": lambda: state_kept_in("bfloat16"),
            "weights=int8": weights_int8,
            "hit=past_snapshot": hit_past_snapshot}


def main(argv):
    from chipbench import correct_olmo_hybrid, run
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
    served = correct_olmo_hybrid.served

    def noted(*a, **kw):
        last["verdict"] = served(*a, **kw)
        return last["verdict"]

    correct_olmo_hybrid.served = noted
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
                run.note(reading=label, seed=seed, code=code, planted=what,
                         numbers=last["verdict"].get("numbers"),
                         tokens_compared=last["verdict"].get(
                             "tokens_compared"),
                         state_by_row=last["verdict"].get("state_by_row"),
                         run_correct=res and res["correct"],
                         metrics=res and res["metrics"])
    finally:
        correct_olmo_hybrid.served = served


@contextmanager
def _nothing():
    yield


if __name__ == "__main__":
    main(sys.argv[1:])
