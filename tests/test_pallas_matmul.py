"""Pallas fused-epilogue GEMM (`ops.pallas.matmul`) vs the naive jnp
composition (interpret mode on CPU): forward + gradients for every
activation, the bf16-operand tolerance policy, the explicit-block-size
contract (explicit beats env, non-divisors raise), the naive fallback
for untileable shapes, and the op-level lowering.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import matmul as M
from paddle_tpu.ops.pallas.matmul import (
    matmul_bias_act,
    naive_matmul_bias_act,
)

# FFN-shaped aspect (M=B*S, K=hidden, N=intermediate) scaled down so the
# interpreter stays fast; every dim is 128-tileable and the 128-block
# choice exercises the multi-block accumulation schedules (2x4x2 grid)
MKN = (256, 256, 512)
BLOCKS = dict(block_m=128, block_n=128, block_k=128)


def _operands(dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    m, k, n = MKN
    x = jnp.asarray(rng.randn(m, k).astype(dtype) * 0.1)
    w = jnp.asarray(rng.randn(k, n).astype(dtype) * 0.1)
    b = jnp.asarray(rng.randn(n).astype(dtype) * 0.1)
    return x, w, b


@pytest.mark.parametrize("act", ["none", "relu", "tanh", "gelu"])
@pytest.mark.parametrize("with_bias", [True, False])
def test_forward_matches_naive(act, with_bias):
    x, w, b = _operands()
    bias = b if with_bias else None
    out = matmul_bias_act(x, w, bias, activation=act, interpret=True,
                          **BLOCKS)
    ref = naive_matmul_bias_act(x, w, bias, activation=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("act", ["none", "relu", "tanh", "gelu"])
def test_grads_match_naive(act):
    """The custom-VJP backward (dZ recomputed in-register, dbias as the
    dW kernel's reduction epilogue) vs jax differentiating the naive
    composition — all three gradients."""
    x, w, b = _operands()

    def f_fused(x, w, b):
        return jnp.sum(matmul_bias_act(x, w, b, activation=act,
                                       interpret=True, **BLOCKS) * 0.01)

    def f_naive(x, w, b):
        return jnp.sum(naive_matmul_bias_act(x, w, b, activation=act)
                       * 0.01)

    gf = jax.grad(f_fused, argnums=(0, 1, 2))(x, w, b)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(x, w, b)
    for a, r, name in zip(gf, gn, ("dx", "dw", "dbias")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5,
            err_msg="%s mismatch (%s)" % (name, act))


def test_grads_no_bias():
    x, w, _ = _operands()
    gf = jax.grad(
        lambda x, w: jnp.sum(matmul_bias_act(
            x, w, activation="gelu", interpret=True, **BLOCKS) * 0.01),
        argnums=(0, 1))(x, w)
    gn = jax.grad(
        lambda x, w: jnp.sum(naive_matmul_bias_act(
            x, w, activation="gelu") * 0.01), argnums=(0, 1))(x, w)
    for a, r, name in zip(gf, gn, ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_approximate_gelu_fwd_and_grad():
    x, w, b = _operands()
    out = matmul_bias_act(x, w, b, activation="gelu", approximate=True,
                          interpret=True, **BLOCKS)
    ref = naive_matmul_bias_act(x, w, b, activation="gelu",
                                approximate=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    gf = jax.grad(lambda x: jnp.sum(matmul_bias_act(
        x, w, b, activation="gelu", approximate=True, interpret=True,
        **BLOCKS) * 0.01))(x)
    gn = jax.grad(lambda x: jnp.sum(naive_matmul_bias_act(
        x, w, b, activation="gelu", approximate=True) * 0.01))(x)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gn),
                               rtol=2e-4, atol=2e-5)


def test_bf16_operand_tolerance_policy():
    """bf16 operands with f32 accumulation: the documented bound —
    forward within 2e-2, gradients within 5e-2 of the f32 oracle."""
    x, w, b = _operands()
    xb, wb, bb = (x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                  b.astype(jnp.bfloat16))
    out = matmul_bias_act(xb, wb, bb, activation="gelu", interpret=True,
                          **BLOCKS)
    ref = naive_matmul_bias_act(x, w, b, activation="gelu")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)

    gf = jax.grad(lambda x_: jnp.sum(matmul_bias_act(
        x_, wb, bb, activation="gelu", interpret=True,
        **BLOCKS).astype(jnp.float32) * 0.01))(xb)
    gn = jax.grad(lambda x_: jnp.sum(naive_matmul_bias_act(
        x_, w, b, activation="gelu") * 0.01))(x)
    np.testing.assert_allclose(np.asarray(gf, np.float32),
                               np.asarray(gn), rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# block-size contract (the tune.search_gemm_blocks knob)
# ---------------------------------------------------------------------------


def test_explicit_non_divisor_block_raises():
    x, w, b = _operands()
    with pytest.raises(ValueError, match="must divide"):
        matmul_bias_act(x, w, b, interpret=True, block_m=96)
    with pytest.raises(ValueError, match="must divide"):
        matmul_bias_act(x, w, b, interpret=True, block_n=200,
                        block_m=128, block_k=128)


def test_explicit_beats_env(monkeypatch):
    """A valid env override must NOT rescue an invalid explicit block:
    explicit args are a hard contract (the tuner must never time a
    different grid than it requested)."""
    x, w, b = _operands()
    monkeypatch.setenv("PADDLE_TPU_GEMM_BLOCKS", "128,128,128")
    with pytest.raises(ValueError, match="must divide"):
        matmul_bias_act(x, w, b, interpret=True, block_m=100)
    # and a valid explicit choice wins over a DIFFERENT valid env one
    grids = []
    real = M.pl.pallas_call

    def spy(kernel, *a, **kw):
        grids.append(kw.get("grid"))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(M.pl, "pallas_call", spy)
    matmul_bias_act(x, w, b, interpret=True, block_m=256, block_n=256,
                    block_k=256)
    m, k, n = MKN
    assert grids[-1] == (m // 256, n // 256, k // 256)


def test_env_applies_when_no_explicit(monkeypatch):
    x, w, b = _operands()
    grids = []
    real = M.pl.pallas_call

    def spy(kernel, *a, **kw):
        grids.append(kw.get("grid"))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(M.pl, "pallas_call", spy)
    monkeypatch.setenv("PADDLE_TPU_GEMM_BLOCKS", "128,128,128")
    matmul_bias_act(x, w, b, interpret=True)
    m, k, n = MKN
    assert grids[-1] == (m // 128, n // 128, k // 128)
    # non-divisible env falls back to the heuristic with a warning
    monkeypatch.setenv("PADDLE_TPU_GEMM_BLOCKS", "96,96,96")
    with pytest.warns(UserWarning, match="does not divide"):
        matmul_bias_act(x, w, b, interpret=True)
    assert grids[-1] == (m // 256, n // 512, k // 256)


def test_partial_explicit_keeps_env_for_other_dims(monkeypatch):
    x, w, b = _operands()
    grids = []
    real = M.pl.pallas_call

    def spy(kernel, *a, **kw):
        grids.append(kw.get("grid"))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(M.pl, "pallas_call", spy)
    monkeypatch.setenv("PADDLE_TPU_GEMM_BLOCKS", "128,128,128")
    matmul_bias_act(x, w, b, interpret=True, block_n=256)
    m, k, n = MKN
    assert grids[-1] == (m // 128, n // 256, k // 128)


def test_untileable_shape_falls_back_to_naive():
    """Dims no block divides run the unfused composition (a PERF
    fallback with a one-time warning, never a silent truncate)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 48).astype(np.float32))
    w = jnp.asarray(rng.randn(48, 33).astype(np.float32))
    b = jnp.asarray(rng.randn(33).astype(np.float32))
    out = matmul_bias_act(x, w, b, activation="relu", interpret=True)
    ref = naive_matmul_bias_act(x, w, b, activation="relu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_bad_activation_and_shapes_raise():
    x, w, b = _operands()
    with pytest.raises(ValueError, match="activation"):
        matmul_bias_act(x, w, b, activation="softmax", interpret=True)
    with pytest.raises(ValueError, match="2-D"):
        matmul_bias_act(x[None], w, b, interpret=True)
    with pytest.raises(ValueError, match="bias"):
        matmul_bias_act(x, w, b[:-1], interpret=True)


# ---------------------------------------------------------------------------
# op-level lowering (the MatmulBiasActFusePass / fused_linear target)
# ---------------------------------------------------------------------------


def test_op_lowering_matches_composed_chain_static():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.nn import functional as F

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8, 16], append_batch_size=False)
        w = layers.create_parameter([16, 32], name="tpm.w")
        b = layers.create_parameter([32], name="tpm.b")
        fused = F.fused_linear(x, w, b, activation="gelu")
        chain = layers.gelu(layers.elementwise_add(
            layers.mul(x, w, x_num_col_dims=2), b, axis=2))
    exe = fluid.Executor()
    xv = np.random.RandomState(0).randn(4, 8, 16).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got, ref = exe.run(main, feed={"x": xv},
                           fetch_list=[fused, chain])
    assert got.shape == (4, 8, 32)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_static_backward_through_fused_op_matches_chain():
    """append_backward's generic vjp_grad differentiates the fused op's
    lowering (custom-VJP on TPU, jnp composition elsewhere): parameter
    grads must match the unfused chain's exactly."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.nn import functional as F

    def build(fused):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[8, 16], append_batch_size=False)
            w = layers.create_parameter([16, 32], name="bwp.w%d" % fused)
            b = layers.create_parameter([32], name="bwp.b%d" % fused)
            if fused:
                out = F.fused_linear(x, w, b, activation="gelu")
            else:
                out = layers.gelu(layers.elementwise_add(
                    layers.mul(x, w), b, axis=1))
            loss = layers.mean(out)
            pg = fluid.append_backward(loss)
        grads = {p.name.rsplit(".", 1)[-1]: g for p, g in pg}
        return main, startup, grads

    xv = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    wv = np.random.RandomState(1).randn(16, 32).astype(np.float32)
    bv = np.random.RandomState(2).randn(32).astype(np.float32)

    results = {}
    for fused in (0, 1):
        import paddle_tpu.fluid as fluid

        main, startup, grads = build(fused)
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            scope = fluid.global_scope()
            scope.set("bwp.w%d" % fused, wv)
            scope.set("bwp.b%d" % fused, bv)
            gw, gb = exe.run(
                main, feed={"x": xv},
                fetch_list=[grads["w%d" % fused], grads["b%d" % fused]])
        results[fused] = (np.asarray(gw), np.asarray(gb))
    np.testing.assert_allclose(results[1][0], results[0][0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(results[1][1], results[0][1],
                               rtol=1e-5, atol=1e-6)


def test_partial_explicit_with_untileable_dim_names_the_dim():
    """When an explicit block is given but a NON-explicit dim has no
    supported tile, the error blames that dim (not the explicit args
    the caller actually passed)."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(100, 256).astype(np.float32))
    w = jnp.asarray(rng.randn(256, 512).astype(np.float32))
    with pytest.raises(ValueError, match="M=100"):
        matmul_bias_act(x, w, interpret=True, block_n=256)


def test_unknown_activation_raises_on_every_path():
    """The naive fallback and the op lowering must reject unknown
    activations exactly like the kernel — never silently return
    un-activated output on one platform while raising on another."""
    x, w, b = _operands()
    with pytest.raises(ValueError, match="activation"):
        naive_matmul_bias_act(x, w, b, activation="sigmoid")

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.layers.common import append_simple_op

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xd = layers.data("x", shape=[4, 16], append_batch_size=False)
        wp = layers.create_parameter([16, 32], name="ua.w")
        # the shape-inference wrapper re-raises with context, so match
        # the message rather than the exact exception type
        with pytest.raises(Exception, match="act_type"):
            append_simple_op("matmul_bias_act", {"X": xd, "Y": wp},
                             {"act_type": "sigmoid",
                              "x_num_col_dims": 1, "y_num_col_dims": 1})


def test_env_blocks_zero_or_negative_raise(monkeypatch):
    x, w, b = _operands()
    monkeypatch.setenv("PADDLE_TPU_GEMM_BLOCKS", "0,128,128")
    with pytest.raises(ValueError, match="POSITIVE"):
        matmul_bias_act(x, w, b, interpret=True)
    monkeypatch.setenv("PADDLE_TPU_GEMM_BLOCKS", "-128,128,128")
    with pytest.raises(ValueError, match="POSITIVE"):
        matmul_bias_act(x, w, b, interpret=True)
