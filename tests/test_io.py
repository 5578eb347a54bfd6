"""IO tests: loader determinism, sharding, native reader (SURVEY.md §4)."""
import numpy as np
import pytest

from paddle_tpu.io import (
    BatchSampler,
    DataLoader,
    Dataset,
    DistributedBatchSampler,
    RandomSampler,
    Subset,
    TensorDataset,
    TokenBinDataset,
    random_split,
)


class _Square(Dataset):
    def __len__(self):
        return 10

    def __getitem__(self, i):
        return np.asarray([i, i * i])


def test_tensor_dataset_and_loader():
    xs = np.arange(20).reshape(10, 2)
    ys = np.arange(10)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=4)
    batches = list(dl)
    assert len(batches) == 3
    assert batches[0][0].shape == (4, 2)
    assert batches[-1][0].shape == (2, 2)
    dl2 = DataLoader(TensorDataset(xs, ys), batch_size=4, drop_last=True)
    assert len(list(dl2)) == 2


def test_shuffle_deterministic_by_seed():
    dl_a = DataLoader(_Square(), batch_size=2, shuffle=True, seed=7)
    dl_b = DataLoader(_Square(), batch_size=2, shuffle=True, seed=7)
    a = [b[0].tolist() for b in dl_a]
    b = [b[0].tolist() for b in dl_b]
    # note: RandomSampler advances epoch per-iteration; same seed, epoch 0
    assert a == b


def test_random_split_and_subset():
    parts = random_split(_Square(), [7, 3])
    assert len(parts[0]) == 7 and len(parts[1]) == 3
    all_firsts = sorted(int(parts[0][i][0]) for i in range(7)) + \
        sorted(int(parts[1][i][0]) for i in range(3))
    assert sorted(all_firsts) == list(range(10))


def test_distributed_batch_sampler_partitions():
    ds = _Square()
    seen = []
    for rank in range(2):
        s = DistributedBatchSampler(ds, batch_size=2, num_replicas=2, rank=rank)
        for batch in s:
            seen += batch
    assert sorted(seen) == list(range(10))


def test_worker_prefetch_loader():
    dl = DataLoader(_Square(), batch_size=3, num_workers=2)
    batches = list(dl)
    assert len(batches) == 4
    flat = np.concatenate([b[:, 0] for b in batches])
    assert sorted(flat.tolist()) == list(range(10))


def test_native_token_bin(tmp_path):
    rs = np.random.RandomState(0)
    tokens = rs.randint(0, 5000, 100_000).astype(np.uint16)
    path = tmp_path / "toks.bin"
    tokens.tofile(path)
    ds = TokenBinDataset(str(path), batch_size=4, seq_len=64, seed=3,
                         num_batches=5)
    assert ds.num_tokens == 100_000
    batches = list(ds)
    assert len(batches) == 5
    for x, y in batches:
        assert x.shape == (4, 64) and y.shape == (4, 64)
        # label is input shifted by one within the same window
        np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
        assert x.min() >= 0 and x.max() < 5000
    # windows must come from the file
    x0 = batches[0][0][0]
    joined = tokens.astype(np.int32)
    pos = np.where(joined == x0[0])[0]
    assert any((joined[p:p + 64] == x0).all() for p in pos if p + 64 <= len(joined))


def test_native_token_bin_stream_depends_on_the_seed_alone(tmp_path):
    """Batch k is keyed by (seed, k) and handed out in order: the stream
    must not depend on how many workers cut it, or on their timing."""
    path = tmp_path / "toks.bin"
    np.arange(4096, dtype=np.uint16).tofile(path)

    def stream(seed, workers):
        ds = TokenBinDataset(str(path), batch_size=2, seq_len=16, seed=seed,
                             num_batches=50, num_workers=workers)
        return np.concatenate([x for x, _ in ds], axis=None).tolist()

    one = stream(7, 1)
    assert stream(7, 4) == one and stream(7, 2) == one
    assert stream(8, 2) != one


# -- multiprocess workers ----------------------------------------------------

class _SquareDataset:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.asarray([i * i], np.int64)


class _FailingDataset(_SquareDataset):
    def __getitem__(self, i):
        if i == 5:
            raise ValueError("boom at 5")
        return super().__getitem__(i)


def test_mp_workers_match_serial():
    from paddle_tpu.io import DataLoader
    ds = _SquareDataset(23)
    serial = [b for b in DataLoader(ds, batch_size=4, num_workers=0)]
    parallel = [b for b in DataLoader(ds, batch_size=4, num_workers=3)]
    assert len(serial) == len(parallel)
    for a, b in zip(serial, parallel):
        np.testing.assert_array_equal(a, b)


def test_mp_workers_shuffle_deterministic():
    from paddle_tpu.io import DataLoader
    ds = _SquareDataset(17)
    a = [b for b in DataLoader(ds, batch_size=4, shuffle=True, seed=7,
                               num_workers=2)]
    b = [b for b in DataLoader(ds, batch_size=4, shuffle=True, seed=7,
                               num_workers=0)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_mp_worker_error_propagates():
    from paddle_tpu.io import DataLoader
    import pytest as _pytest
    ds = _FailingDataset(8)
    with _pytest.raises(RuntimeError, match="boom at 5"):
        list(DataLoader(ds, batch_size=2, num_workers=2))


def test_get_worker_info():
    from paddle_tpu.io import DataLoader, get_worker_info
    assert get_worker_info() is None

    class _InfoDataset(_SquareDataset):
        def __getitem__(self, i):
            info = get_worker_info()
            assert info is not None and 0 <= info.id < info.num_workers
            return np.asarray([info.num_workers], np.int64)

    out = list(DataLoader(_InfoDataset(6), batch_size=2, num_workers=2))
    assert all(int(b[0, 0]) == 2 for b in out)


def test_threaded_iterable_error_propagates():
    from paddle_tpu.io import DataLoader, IterableDataset
    import pytest as _pytest

    class _Boom(IterableDataset):
        def __iter__(self):
            yield np.zeros(1)
            raise ValueError("iterable boom")

    with _pytest.raises(ValueError, match="iterable boom"):
        list(DataLoader(_Boom(), batch_size=1, num_workers=1))


def test_worker_seed_from_loader_seed():
    from paddle_tpu.io import DataLoader, get_worker_info

    class _SeedDataset(_SquareDataset):
        def __getitem__(self, i):
            return np.asarray([get_worker_info().seed], np.int64)

    out = list(DataLoader(_SeedDataset(4), batch_size=1, num_workers=2,
                          seed=1234))
    seeds = {int(b[0, 0]) for b in out}
    assert seeds <= {1234, 1235} and len(seeds) >= 1
