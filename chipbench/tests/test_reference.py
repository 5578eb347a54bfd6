"""The plain reference against the program's own full forward, float32 on
the CPU at a toy size: same seeded weights, same tokens."""
import json
from pathlib import Path

import numpy as np

CFG = json.loads((Path(__file__).parent / "cells" / "configs"
                  / "tiny.json").read_text())


def test_reference_matches_the_programs_forward():
    import jax.numpy as jnp

    from chipbench import reference, weights
    from chipbench.builders import llama

    seed = 2 ** 31 + 11
    model = llama.build(CFG, seed).eval()
    ids = np.random.default_rng(0).integers(1, CFG["vocab_size"], (3, 40),
                                            dtype=np.int32)
    want = np.asarray(model(jnp.asarray(ids)), np.float32)
    got = reference.forward(CFG, list(ids), weights.make_top(seed, CFG),
                            lambda i: weights.make_layer(seed, i, CFG))
    assert np.abs(want).max() > 0.1
    for k in range(3):
        np.testing.assert_allclose(np.asarray(got[k]), want[k], atol=2e-5)


def test_the_lowered_copy_of_the_layer_is_the_layer_but_for_its_rounding():
    """``control.lowered`` swaps in a copy of ``reference.layer`` that rounds:
    rounding to float32 changes nothing, so the copy must give the
    reference's logits bit for bit; rounding to int8 must move them."""
    from chipbench import control, reference, weights
    seed = 5
    ids = [np.arange(1, 33, dtype=np.int32)]
    top = weights.make_top(seed, CFG)
    lw = lambda i: weights.make_layer(seed, i, CFG)
    plain = np.asarray(reference.forward(CFG, ids, top, lw)[0])
    with control.lowered("float32+act"):
        same = np.asarray(reference.forward(CFG, ids, top, lw)[0])
    with control.lowered("int8"):
        low = np.asarray(reference.forward(CFG, ids, top, lw)[0])
    again = np.asarray(reference.forward(CFG, ids, top, lw)[0])
    assert np.array_equal(plain, same) and np.array_equal(plain, again)
    assert 1e-4 < np.abs(plain - low).max() < 0.1 * np.abs(plain).max()
    kept = reference.forward(CFG, ids, top, lw, keep=[np.array([3, 31, 31])])
    np.testing.assert_allclose(np.asarray(kept[0]), plain[[3, 31, 31]],
                               rtol=0, atol=1e-6)
