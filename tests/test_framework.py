"""IR + executor + autodiff basics (cf. reference tests/unittests/
test_program.py, test_executor_*, test_backward.py)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


def test_program_build_and_shapes():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 3], append_batch_size=False)
        y = layers.fc(x, size=8, act="relu")
    assert y.shape == (4, 8)
    assert len(main.global_block.ops) >= 2
    params = main.all_parameters()
    assert len(params) == 2  # weight + bias


def test_dynamic_batch_dim():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[3])  # implicit -1 batch
        y = layers.fc(x, size=8)
    assert y.shape == (-1, 8)


def test_executor_simple_run():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[2, 3], append_batch_size=False)
        y = layers.relu(x)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.array([[-1.0, 2.0, -3.0], [4.0, -5.0, 6.0]], dtype=np.float32)
    (out,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(out, np.maximum(xv, 0))


def test_executor_persistable_params():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[5, 3], append_batch_size=False)
        y = layers.fc(x, size=4)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.random.RandomState(0).randn(5, 3).astype(np.float32)
    (out,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    assert out.shape == (5, 4)
    # parity check vs numpy using the actual initialized weights
    w_name = main.all_parameters()[0].name
    b_name = main.all_parameters()[1].name
    w = np.asarray(fluid.global_scope().find_var(w_name))
    b = np.asarray(fluid.global_scope().find_var(b_name))
    np.testing.assert_allclose(out, xv @ w + b, rtol=1e-5, atol=1e-5)


def test_program_serialization_roundtrip():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[2, 3], append_batch_size=False)
        y = layers.fc(x, size=4, act="tanh")
    s = main.to_json()
    clone = fluid.Program.from_json(s)
    assert len(clone.global_block.ops) == len(main.global_block.ops)
    # run the deserialized program
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.ones((2, 3), dtype=np.float32)
    (a,) = exe.run(main, feed={"x": xv}, fetch_list=[y.name])
    (b,) = exe.run(clone, feed={"x": xv}, fetch_list=[y.name])
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_append_backward_simple():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 3], append_batch_size=False)
        x.stop_gradient = False
        y = layers.fc(x, size=1, bias_attr=False)
        loss = layers.mean(y)
        pg = fluid.append_backward(loss)
    assert len(pg) == 1
    p, g = pg[0]
    assert g.name == p.name + "@GRAD"

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.random.RandomState(1).randn(4, 3).astype(np.float32)
    (gv,) = exe.run(main, feed={"x": xv}, fetch_list=[g])
    # d mean(xW) / dW = mean over batch of x / 1 => x.mean(0) / 1
    np.testing.assert_allclose(gv[:, 0], xv.mean(axis=0) / 1.0, rtol=1e-5, atol=1e-5)


def test_grad_accumulation_multi_consumer():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[3], append_batch_size=False)
        x.stop_gradient = False
        a = x * x  # consumer 1+2 of x
        b = x + a
        loss = layers.reduce_sum(b)
        fluid.append_backward(loss, parameter_list=[])
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.array([1.0, 2.0, 3.0], dtype=np.float32)
    (gx,) = exe.run(main, feed={"x": xv}, fetch_list=["x@GRAD"])
    np.testing.assert_allclose(gx, 1.0 + 2 * xv, rtol=1e-5)


def test_sgd_training_decreases_loss():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 4], append_batch_size=False)
        label = layers.data("y", shape=[8, 1], append_batch_size=False)
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, label))
        from paddle_tpu.fluid.optimizer import SGDOptimizer

        SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rs = np.random.RandomState(7)
    xv = rs.randn(8, 4).astype(np.float32)
    w_true = rs.randn(4, 1).astype(np.float32)
    yv = xv @ w_true
    losses = []
    for _ in range(30):
        (lv,) = exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[loss])
        losses.append(float(lv))
    assert losses[-1] < losses[0] * 0.2, losses


def test_clone_for_test_strips_optimizer():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 4], append_batch_size=False)
        h = layers.fc(x, size=4)
        h = layers.dropout(h, dropout_prob=0.5)
        loss = layers.mean(h)
        from paddle_tpu.fluid.optimizer import SGDOptimizer

        SGDOptimizer(0.1).minimize(loss)
    test_prog = main.clone(for_test=True)
    types = [op.type for op in test_prog.global_block.ops]
    assert "sgd" not in types
    drop_ops = [op for op in test_prog.global_block.ops if op.type == "dropout"]
    assert all(op.attrs["is_test"] for op in drop_ops)


# ---------------------------------------------------------------------------
# Place resolution and one-process-per-chip (PR 22)
# ---------------------------------------------------------------------------


def test_place_names_a_real_device_or_raises():
    """A place never resolves to another device than it names: a
    `TPUPlace` that ran on the CPU would report CPU results under the
    chip's name."""
    import jax

    assert fluid.CPUPlace(0).get_device() == jax.devices("cpu")[0]
    assert fluid.CPUPlace(7).get_device() == jax.devices("cpu")[7]
    with pytest.raises(RuntimeError, match="names no device"):
        fluid.TPUPlace(0).get_device()          # no TPU in this process
    with pytest.raises(RuntimeError, match="names no device"):
        fluid.CPUPlace(99).get_device()         # past the last device
    with pytest.raises(RuntimeError, match="names no device"):
        fluid.Executor(fluid.TPUPlace(0)).run(fluid.Program())


def test_children_that_need_the_chip_are_refused_by_a_parent_that_holds_it(
        monkeypatch):
    import jax

    from paddle_tpu.fluid.core.place import check_children_can_take_chip

    jax.devices()                               # a backend is initialized
    # the CPU is not exclusive: a CPU parent may start anything
    check_children_can_take_chip("workers", {"JAX_PLATFORMS": "tpu"})
    # a parent that holds an accelerator may start only CPU children
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    check_children_can_take_chip("workers", {"JAX_PLATFORMS": "cpu"})
    with pytest.raises(RuntimeError, match="one process at a time"):
        check_children_can_take_chip("workers", {})
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="cannot start ranks"):
        check_children_can_take_chip("ranks")   # child env defaults to ours


def test_cpu_only_children_restores_the_environment(monkeypatch):
    import os

    from paddle_tpu.fluid.core.place import cpu_only_children

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with cpu_only_children():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in os.environ
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with cpu_only_children():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
