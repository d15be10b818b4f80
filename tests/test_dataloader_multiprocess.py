"""Multi-process DataLoader workers (reference
_DataLoaderIterMultiProcess, python/paddle/io/dataloader/dataloader_iter.py:358).
"""

import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset, IterableDataset, get_worker_info


class _PidDataset(Dataset):
    """Each sample records the worker's PID so the test can prove samples
    were produced by real separate processes."""

    def __len__(self):
        return 16

    def __getitem__(self, i):
        return np.asarray([i, os.getpid()], dtype=np.int64)


class _SleepDataset(Dataset):
    """Each sample records its worker's PID and when its 0.1 s of
    "work" began and ended, on the clock every process of the machine
    shares (counted from the dataset's birth: batches come back as
    float32)."""

    def __init__(self):
        self._born = time.monotonic()

    def __len__(self):
        return 8

    def __getitem__(self, i):
        t0 = time.monotonic() - self._born
        time.sleep(0.1)
        return np.asarray([os.getpid(), t0, time.monotonic() - self._born],
                          np.float32)


def test_process_workers_real_processes_and_order():
    dl = DataLoader(_PidDataset(), batch_size=4, num_workers=2)
    rows = []
    for batch in dl:
        rows.append(np.asarray(batch._value))
    got = np.concatenate(rows)
    # batch order preserved (reorder buffer), indices 0..15 in order
    np.testing.assert_array_equal(got[:, 0], np.arange(16))
    # samples came from worker processes, not this one
    pids = set(got[:, 1].tolist())
    assert os.getpid() not in pids
    assert len(pids) == 2  # both workers participated


def test_process_workers_overlap_wallclock():
    # 8 samples x 0.1 s sleep over 2 workers: the workers overlap when
    # a sample of one was in flight while a sample of the other was.
    # Read from the samples' own intervals, not from the loop's wall
    # time, which a loaded box stretches (it failed so once, PR 28).
    # (GIL-bound compute overlaps the same way on multi-core hosts; sleep
    # is used here because CI has a single core.)
    dl = DataLoader(_SleepDataset(), batch_size=2, num_workers=2)
    rows = np.concatenate([np.asarray(b._value) for b in dl])
    assert rows.shape == (8, 3)
    pid, t0, t1 = rows.T
    both = (pid[:, None] != pid[None, :]) \
        & (t0[:, None] < t1[None, :]) & (t0[None, :] < t1[:, None])
    assert both.any(), "no worker overlap"


def test_worker_info_in_child():
    class _InfoDataset(Dataset):
        def __len__(self):
            return 4

        def __getitem__(self, i):
            info = get_worker_info()
            assert info is not None
            return np.asarray([i, info.id, info.num_workers], np.int64)

    dl = DataLoader(_InfoDataset(), batch_size=2, num_workers=2)
    out = np.concatenate([np.asarray(b._value) for b in dl])
    assert set(out[:, 2].tolist()) == {2}
    assert set(out[:, 1].tolist()) <= {0, 1}


def test_worker_exception_propagates():
    class _Boom(Dataset):
        def __len__(self):
            return 4

        def __getitem__(self, i):
            if i == 2:
                raise ValueError("bad sample 2")
            return np.asarray([i], np.int64)

    dl = DataLoader(_Boom(), batch_size=2, num_workers=2)
    with pytest.raises(RuntimeError, match="bad sample 2"):
        list(dl)


def test_iterable_dataset_multiprocess_sharding():
    class _Shards(IterableDataset):
        def __iter__(self):
            info = get_worker_info()
            # classic worker-shard pattern from the reference docs
            for i in range(info.id, 8, info.num_workers):
                yield np.asarray([i], np.int64)

    dl = DataLoader(_Shards(), batch_size=2, num_workers=2)
    vals = sorted(
        int(v) for b in dl for v in np.asarray(b._value).reshape(-1))
    assert vals == list(range(8))


def test_thread_workers_still_available():
    dl = DataLoader(_PidDataset(), batch_size=4, num_workers=2,
                    use_process_workers=False)
    got = np.concatenate([np.asarray(b._value) for b in dl])
    np.testing.assert_array_equal(got[:, 0], np.arange(16))
    assert set(got[:, 1].tolist()) == {os.getpid()}  # same process
