"""py_func + the public custom-op extension story (reference
`tests/unittests/test_py_func_op.py` and `tests/custom_op/`)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


def test_py_func_forward_and_backward():
    """Ported reference pattern: tanh via py_func with a hand backward;
    grads flow through the host callback."""

    def my_tanh(x):
        return np.tanh(x)

    def my_tanh_grad(x, y, dy):
        return dy * (1.0 - np.square(np.tanh(x)))

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 4], append_batch_size=False)
        hidden = layers.fc(x, size=4, param_attr="pyf_fc.w")
        out_var = layers.nn.create_tmp_var("pyf_out", "float32", [-1, 4])
        layers.py_func(my_tanh, hidden, out_var,
                       backward_func=my_tanh_grad)
        loss = layers.reduce_mean(layers.square(out_var))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)

    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    xv = rng.randn(8, 4).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        losses = []
        for _ in range(6):
            (lv,) = exe.run(main, feed={"x": xv}, fetch_list=[loss])
            losses.append(float(lv))
    # training through the py_func backward reduces the loss
    assert losses[-1] < losses[0] * 0.9, losses


def test_py_func_output_value_matches_numpy():
    def double_plus(x, y):
        return x * 2.0 + y

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.data("a", shape=[-1, 3], append_batch_size=False)
        b = layers.data("b", shape=[-1, 3], append_batch_size=False)
        o = layers.nn.create_tmp_var("pyf_o2", "float32", [-1, 3])
        layers.py_func(double_plus, [a, b], o)
        out = o * 1.0
    exe = fluid.Executor()
    rng = np.random.RandomState(1)
    av = rng.randn(2, 3).astype(np.float32)
    bv = rng.randn(2, 3).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (got,) = exe.run(main, feed={"a": av, "b": bv}, fetch_list=[out])
    np.testing.assert_allclose(got, av * 2 + bv, rtol=1e-6)


def test_py_func_without_backward_stops_gradients():
    def ident(x):
        return x

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 3], append_batch_size=False)
        h = layers.fc(x, size=3, param_attr="pyf_fc2.w", bias_attr=False)
        o = layers.nn.create_tmp_var("pyf_o3", "float32", [-1, 3])
        layers.py_func(ident, h, o)
        loss = layers.reduce_mean(layers.square(o))
        fluid.optimizer.SGDOptimizer(0.5).minimize(loss)
    exe = fluid.Executor()
    xv = np.ones((4, 3), np.float32)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.find_var("pyf_fc2.w")).copy()
        exe.run(main, feed={"x": xv}, fetch_list=[loss])
        w1 = np.asarray(scope.find_var("pyf_fc2.w"))
    np.testing.assert_allclose(w0, w1)  # no grads flowed


def test_custom_op_registration_from_user_code():
    """The public extension API (reference tests/custom_op/): a USER
    module registers a brand-new op type with register_op; JAX AD gives
    its gradient; layers drive it through a Program."""
    import jax.numpy as jnp

    from paddle_tpu.fluid.core import registry
    from paddle_tpu.fluid.core.registry import register_op

    @register_op("user_relu3", inputs=["X"], outputs=["Out"])
    def _user_relu3(ctx, ins, attrs):
        """User op: relu(x)^3, scaled by an attr."""
        x = ins["X"][0]
        s = float(attrs.get("scale", 1.0))
        return {"Out": [jnp.maximum(x, 0.0) ** 3 * s]}

    try:
        _run_user_relu3()
    finally:
        # the op table is process-wide: a later test in this worker
        # (test_api_spec) must not see this test's op in the surface
        registry._OP_REGISTRY.pop("user_relu3")


def _run_user_relu3():
    from paddle_tpu.fluid.layers.common import append_simple_op

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 5], append_batch_size=False)
        x.stop_gradient = False
        y = append_simple_op("user_relu3", {"X": x}, {"scale": 2.0})
        loss = layers.reduce_sum(y)
        grads = fluid.backward.gradients([loss], [x])
    exe = fluid.Executor()
    rng = np.random.RandomState(2)
    xv = rng.randn(3, 5).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got_y, got_gx = exe.run(
            main, feed={"x": xv}, fetch_list=[y, grads[0]])
    ref_y = np.maximum(xv, 0) ** 3 * 2.0
    ref_gx = 3 * np.maximum(xv, 0) ** 2 * 2.0 * (xv > 0)
    np.testing.assert_allclose(got_y, ref_y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_gx, ref_gx, rtol=1e-4, atol=1e-5)
