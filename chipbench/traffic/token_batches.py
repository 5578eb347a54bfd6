"""``token_batches``: training batches of random tokens, made on the host.
Step k of seed s is a pure function of (s, k): rows that all differ, labels
the inputs shifted by one with the last position ignored (-100)."""
import numpy as np


def batch(seed: int, step: int, params: dict, vocab_size: int):
    """-> (ids [batch, seq_len] int32, labels [batch, seq_len] int32)."""
    rng = np.random.default_rng([int(seed), 0xBA7C, int(step)])
    ids = rng.integers(0, vocab_size, (params["batch"], params["seq_len"]),
                       dtype=np.int32)
    labels = np.concatenate(
        [ids[:, 1:], np.full((params["batch"], 1), -100, np.int32)], axis=1)
    return ids, labels
