"""save/load, inference export, DataLoader, LR schedules (cf. reference
test_io_save_load*, test_dataloader*, test_learning_rate_scheduler)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import io, layers
from paddle_tpu.fluid.layers import learning_rate_scheduler as lrs
from paddle_tpu.fluid.optimizer import AdamOptimizer, SGDOptimizer
from paddle_tpu.fluid.reader import BatchSampler, DataLoader, TensorDataset, batch, shuffle


def _small_model():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1], dtype="int64")
        logits = layers.fc(x, 3)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
    return main, startup, x, y, logits, loss


def test_save_load_roundtrip(tmp_path):
    main, startup, *_ = _small_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    path = str(tmp_path / "model")
    io.save(main, path)
    w = main.all_parameters()[0]
    orig = np.asarray(fluid.global_scope().find_var(w.name)).copy()
    fluid.global_scope().set(w.name, np.zeros_like(orig))
    io.load(main, path)
    np.testing.assert_allclose(
        np.asarray(fluid.global_scope().find_var(w.name)), orig
    )


def test_save_persistables_includes_optimizer_state(tmp_path):
    main, startup, x, y, logits, loss = _small_model()
    with fluid.program_guard(main, startup):
        AdamOptimizer(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((2, 4), np.float32),
                        "y": np.zeros((2, 1), np.int64)}, fetch_list=[loss])
    d = str(tmp_path / "persist")
    io.save_persistables(exe, d, main)
    import os

    files = os.listdir(d)
    assert any("moment1" in f for f in files), files  # adam state saved


def test_inference_export_prunes_and_runs(tmp_path):
    main, startup, x, y, logits, loss = _small_model()
    with fluid.program_guard(main, startup):
        SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / "infer")
    io.save_inference_model(d, ["x"], [logits], exe, main)
    prog, feeds, fetches = io.load_inference_model(d, exe)
    types = [op.type for op in prog.global_block.ops]
    assert "sgd" not in types and "vjp_grad" not in types
    assert "softmax_with_cross_entropy" not in types  # pruned past target
    (out,) = exe.run(prog, feed={"x": np.ones((5, 4), np.float32)},
                     fetch_list=fetches)
    assert out.shape == (5, 3)


def test_dataloader_map_style():
    ds = TensorDataset(np.arange(20, dtype=np.float32).reshape(10, 2),
                       np.arange(10, dtype=np.int64))
    loader = DataLoader(ds, batch_size=4, shuffle=False)
    batches = list(loader)
    assert len(batches) == 3
    assert batches[0][0].shape == (4, 2)
    np.testing.assert_array_equal(batches[0][1], [0, 1, 2, 3])


def test_dataloader_generator_mode():
    def gen():
        for i in range(7):
            yield [np.full((2,), i, np.float32), np.array([i], np.int64)]

    loader = DataLoader.from_generator(capacity=2)
    loader.set_sample_list_generator(lambda: (list(g) for g in _chunks(gen(), 2)))
    got = list(loader)
    assert len(got) == 4


def _chunks(it, n):
    buf = []
    for x in it:
        buf.append(x)
        if len(buf) == n:
            yield buf
            buf = []
    if buf:
        yield buf


def test_reader_decorators():
    r = batch(lambda: iter(range(10)), 3)
    out = list(r())
    assert out[0] == [0, 1, 2] and len(out) == 4
    s = shuffle(lambda: iter(range(10)), 5, seed=0)
    assert sorted(list(s())) == list(range(10))


def test_noam_decay_warmup_then_decay():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[2])
        out = layers.fc(x, 1)
        loss = layers.mean(out)
        lr = lrs.noam_decay(d_model=64, warmup_steps=5, learning_rate=1.0)
        SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    seen = []
    for _ in range(12):
        _, lrv = exe.run(
            main,
            feed={"x": np.ones((2, 2), np.float32)},
            fetch_list=[loss, lr],
        )
        seen.append(float(lrv[0]))
    assert seen[0] < seen[2] < seen[4]  # warming up
    assert seen[11] < seen[4]  # decaying after warmup_steps


def test_piecewise_decay():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[2])
        loss = layers.mean(layers.fc(x, 1))
        lr = lrs.piecewise_decay([3, 6], [0.1, 0.01, 0.001])
        SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    seen = []
    for _ in range(8):
        (lrv,) = exe.run(main, feed={"x": np.ones((1, 2), np.float32)},
                         fetch_list=[lr])
        seen.append(round(float(lrv[0]), 6))
    # counter starts at 1 after first increment
    assert seen[0] == 0.1 and seen[3] == 0.01 and seen[7] == 0.001, seen


# ---------------------------------------------------------------------------
# multiprocess DataLoader workers (reference dataloader_iter.py capability)
# ---------------------------------------------------------------------------


class _SlowDataset:
    """Map-style dataset with per-item parse cost (simulates decode)."""

    def __init__(self, n=64):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        x = np.full((4,), float(i), np.float32)
        return x, np.int64(i % 3)


def test_dataloader_multiprocess_order_and_content():
    from paddle_tpu.fluid.reader import DataLoader

    ds = _SlowDataset(40)
    dl = DataLoader(ds, batch_size=8, num_workers=3, shuffle=False)
    seen = []
    for bx, by in dl:
        assert bx.shape == (8, 4)
        seen.extend(bx[:, 0].astype(int).tolist())
    assert seen == list(range(40)), "batches out of order or missing"


def test_dataloader_multiprocess_matches_single_process():
    from paddle_tpu.fluid.reader import DataLoader

    ds = _SlowDataset(33)
    single = [b for b in DataLoader(ds, batch_size=5, num_workers=0)]
    multi = [b for b in DataLoader(ds, batch_size=5, num_workers=2)]
    assert len(single) == len(multi)
    for (sx, sy), (mx, my) in zip(single, multi):
        np.testing.assert_array_equal(sx, mx)
        np.testing.assert_array_equal(sy, my)


class _PoisonDataset(_SlowDataset):
    """Module-level: spawn workers must pickle the dataset."""

    def __getitem__(self, i):
        if i == 7:
            raise ValueError("poison item")
        return super().__getitem__(i)


def test_dataloader_worker_error_propagates():
    from paddle_tpu.fluid.reader import DataLoader

    dl = DataLoader(_PoisonDataset(16), batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="worker failed"):
        list(dl)


class _EnvDataset(_SlowDataset):
    """Each item reports whether its worker is pinned to the CPU."""

    def __getitem__(self, i):
        import os

        pinned = os.environ.get("JAX_PLATFORMS") == "cpu"
        return np.full((1,), float(pinned), np.float32), np.int64(0)


def test_dataloader_workers_are_pinned_to_the_cpu(monkeypatch):
    """A chip belongs to one process at a time: workers only collate on
    the host, so they must never initialize the parent's accelerator
    backend — whatever the parent's own JAX_PLATFORMS says."""
    import os

    from paddle_tpu.fluid.reader import DataLoader

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    dl = DataLoader(_EnvDataset(8), batch_size=4, num_workers=2)
    try:
        for bx, _ in dl:
            assert bx.min() == 1.0, "a worker saw the parent's platforms"
    finally:
        dl.close()
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"    # parent untouched


def test_distributed_batch_sampler_partitions_and_pads():
    from paddle_tpu.fluid.reader import DistributedBatchSampler, TensorDataset

    ds = TensorDataset(np.arange(10))
    samplers = [
        DistributedBatchSampler(ds, batch_size=2, num_replicas=3, rank=r)
        for r in range(3)
    ]
    per_rank = [[i for b in s for i in b] for s in samplers]
    # equal batch counts per rank; union covers the dataset
    assert len({len(p) for p in per_rank}) == 1
    assert set().union(*map(set, per_rank)) == set(range(10))
    # shuffling reorders deterministically per epoch
    s = DistributedBatchSampler(ds, batch_size=2, num_replicas=1, rank=0,
                                shuffle=True, seed=3)
    s.set_epoch(0)
    e0 = [i for b in s for i in b]
    s.set_epoch(1)
    e1 = [i for b in s for i in b]
    s.set_epoch(0)
    e0b = [i for b in s for i in b]
    assert e0 == e0b and e0 != e1


def _spawn_worker(rank, out_dir):
    import os

    with open(os.path.join(out_dir, "r%d.txt" % rank), "w") as f:
        f.write("%s %s" % (os.environ["PADDLE_TRAINER_ID"],
                           os.environ["PADDLE_TRAINERS_NUM"]))


def test_distributed_spawn(tmp_path):
    from paddle_tpu.distributed.parallel import spawn

    spawn(_spawn_worker, args=(str(tmp_path),), nprocs=2)
    for r in range(2):
        with open(tmp_path / ("r%d.txt" % r)) as f:
            assert f.read() == "%d 2" % r
