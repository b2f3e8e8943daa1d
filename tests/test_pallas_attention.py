"""Pallas flash-attention kernels vs naive oracle (interpret mode on CPU).

Mirrors the reference fused-op test pattern (fused kernel vs composed ops,
cf. test_fused_multihead_matmul_op.py): forward + gradients, with/without
causal masking and padding bias.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import _naive_attention
from paddle_tpu.ops.pallas.attention import flash_attention


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_naive(causal):
    B, H, S, D = 2, 2, 256, 128
    q, k, v = _rand((B, H, S, D), 0), _rand((B, H, S, D), 1), _rand((B, H, S, D), 2)
    scale = D ** -0.5
    out = flash_attention(q, k, v, scale=scale, causal=causal, interpret=True)
    ref = _naive_attention(q, k, v, None, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_forward_with_padding_bias():
    B, H, S, D = 1, 2, 256, 128
    q, k, v = _rand((B, H, S, D), 3), _rand((B, H, S, D), 4), _rand((B, H, S, D), 5)
    mask = np.ones((B, 1, 1, S), np.float32)
    mask[:, :, :, S // 2:] = -10000.0  # pad out second half
    bias = jnp.asarray(mask * 0 + np.where(mask > 0, 0.0, -10000.0))
    bias = jnp.asarray(np.where(np.arange(S)[None, None, None, :] < S // 2, 0.0,
                                -10000.0).astype(np.float32))
    scale = D ** -0.5
    out = flash_attention(q, k, v, bias=bias, scale=scale, interpret=True)
    ref = _naive_attention(q, k, v, bias, scale, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_naive(causal):
    B, H, S, D = 1, 1, 256, 128
    q, k, v = _rand((B, H, S, D), 6), _rand((B, H, S, D), 7), _rand((B, H, S, D), 8)
    scale = D ** -0.5

    def f_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, scale=scale, causal=causal, interpret=True)
            * 0.01
        )

    def f_naive(q, k, v):
        return jnp.sum(_naive_attention(q, k, v, None, scale, causal) * 0.01)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn, name in zip(g_flash, g_naive, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gn), rtol=5e-4, atol=5e-4,
            err_msg="d%s mismatch" % name,
        )


def test_flash_non_divisible_seq_falls_back():
    """S=192 divides no supported block: must fall back to naive, never
    silently truncate."""
    B, H, S, D = 1, 1, 192, 128
    q, k, v = _rand((B, H, S, D), 20), _rand((B, H, S, D), 21), _rand((B, H, S, D), 22)
    out = flash_attention(q, k, v, interpret=True)
    ref = _naive_attention(q, k, v, None, D ** -0.5, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_backward_with_bias_grad():
    B, H, S, D = 1, 2, 256, 128
    q, k, v = _rand((B, H, S, D), 9), _rand((B, H, S, D), 10), _rand((B, H, S, D), 11)
    bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    scale = D ** -0.5

    def f_flash(q, k, v, b):
        return jnp.sum(flash_attention(q, k, v, bias=b, scale=scale,
                                       interpret=True) * 0.01)

    def f_naive(q, k, v, b):
        return jnp.sum(_naive_attention(q, k, v, b, scale, False) * 0.01)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v, bias)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v, bias)
    for a, b_, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg="d%s mismatch" % name)


@pytest.mark.parametrize("sq,sk", [(200, 200), (96, 96), (300, 260)])
def test_flash_pad_to_block_matches_naive(sq, sk):
    """Non-128-divisible seqs keep the kernel path via pad+slice."""
    B, H, D = 1, 2, 128
    q, k, v = _rand((B, H, sq, D), 20), _rand((B, H, sk, D), 21), _rand((B, H, sk, D), 22)
    scale = D ** -0.5
    out = flash_attention(q, k, v, scale=scale, interpret=True)
    ref = _naive_attention(q, k, v, None, scale, False)
    assert out.shape == (B, H, sq, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_pad_causal_and_grads():
    import jax

    B, H, S, D = 1, 1, 200, 128
    q, k, v = _rand((B, H, S, D), 23), _rand((B, H, S, D), 24), _rand((B, H, S, D), 25)
    scale = D ** -0.5
    out = flash_attention(q, k, v, scale=scale, causal=True, interpret=True)
    ref = _naive_attention(q, k, v, None, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    g1 = jax.grad(lambda q_: (flash_attention(q_, k, v, scale=scale,
                                              causal=True,
                                              interpret=True) ** 2).sum())(q)
    g2 = jax.grad(lambda q_: (_naive_attention(q_, k, v, None, scale,
                                               True) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-3, atol=2e-3)


def test_flash_pad_with_segments_and_bias():
    B, H, S, D = 1, 1, 200, 128
    q, k, v = _rand((B, H, S, D), 26), _rand((B, H, S, D), 27), _rand((B, H, S, D), 28)
    seg = jnp.asarray(
        np.repeat([1, 2], [80, 120])[None, :].astype(np.int32))
    scale = D ** -0.5
    from paddle_tpu.ops.attention import _segment_bias

    out = flash_attention(q, k, v, segment_ids=seg, scale=scale,
                          interpret=True)
    ref = _naive_attention(q, k, v, _segment_bias(seg), scale, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_causal_cross_attention_bottom_right_aligned():
    """sq != sk causal must match the naive tril(k=Sk-Sq) alignment."""
    B, H, D = 1, 1, 128
    for sq, sk in [(128, 256), (200, 260), (256, 128)]:
        q = _rand((B, H, sq, D), 30)
        k = _rand((B, H, sk, D), 31)
        v = _rand((B, H, sk, D), 32)
        scale = D ** -0.5
        out = flash_attention(q, k, v, scale=scale, causal=True,
                              interpret=True)
        ref = _naive_attention(q, k, v, None, scale, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4,
            err_msg="sq=%d sk=%d" % (sq, sk),
        )
        # causal CROSS-attention gradients (all three operands)
        import jax as _jax

        g1 = _jax.grad(
            lambda q_, k_, v_: (flash_attention(
                q_, k_, v_, scale=scale, causal=True, interpret=True,
            ) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        g2 = _jax.grad(
            lambda q_, k_, v_: (_naive_attention(
                q_, k_, v_, None, scale, True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a_, b_ in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a_), np.asarray(b_), rtol=2e-3, atol=2e-3,
                err_msg="grad sq=%d sk=%d" % (sq, sk),
            )


def test_flash_head_dim_64():
    """BERT-shaped heads (d=64) must take the kernel path (the head dim is
    never split; its block equals the full dim)."""
    import jax

    B, H, S, D = 1, 2, 256, 64
    q, k, v = _rand((B, H, S, D), 40), _rand((B, H, S, D), 41), _rand((B, H, S, D), 42)
    scale = D ** -0.5
    out = flash_attention(q, k, v, scale=scale, interpret=True)
    ref = _naive_attention(q, k, v, None, scale, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    g1 = jax.grad(lambda q_: (flash_attention(q_, k, v, scale=scale,
                                              interpret=True) ** 2).sum())(q)
    g2 = jax.grad(lambda q_: (_naive_attention(q_, k, v, None, scale,
                                               False) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_bshd_layout_matches_bhsd(causal):
    """BSHD (no-transpose) layout must agree with the BHSD path, forward
    and gradients, with segments + bias."""
    B, H, S, D = 2, 3, 256, 64
    q, k, v = (_rand((B, H, S, D), i) for i in range(3))
    bias = jnp.where(
        jnp.arange(S)[None, None, None, :] < S - 17, 0.0, -1e30
    ).astype(jnp.float32) * jnp.ones((B, 1, 1, S))
    seg = jnp.asarray(
        np.random.RandomState(7).randint(0, 3, (B, S)).cumsum(axis=1) // 7
    )

    def f_bhsd(q, k, v):
        return flash_attention(q, k, v, bias=bias, segment_ids=seg,
                               causal=causal, interpret=True).sum()

    def f_bshd(q, k, v):
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        return flash_attention(qt, kt, vt, bias=bias, segment_ids=seg,
                               causal=causal, interpret=True,
                               layout="BSHD").sum()

    o1, g1 = jax.value_and_grad(f_bhsd, argnums=(0, 1, 2))(q, k, v)
    o2, g2 = jax.value_and_grad(f_bshd, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(o1), float(o2), rtol=1e-4)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_bshd_pad_path():
    B, H, S, D = 1, 2, 200, 64  # pads to 256
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))
    out = flash_attention(q, k, v, interpret=True, layout="BSHD")
    ref = _naive_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), None, D ** -0.5, False
    ).transpose(0, 2, 1, 3)
    assert out.shape == (B, S, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fused_single_block_backward_matches_two_kernel():
    """One block a side (nq == nk == 1, the flagship's schedule) takes
    the fused backward, explicit smaller blocks the two-kernel one: both
    must give the same gradients, including bias and segment ids."""
    rng = np.random.RandomState(3)
    B, H, S, D = 2, 2, 256, 64
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    bias = jnp.asarray(
        np.where(rng.rand(B, 1, 1, S) < 0.2, -1e30, 0.0).astype(np.float32))
    scale = D ** -0.5

    seg = jnp.asarray(
        np.repeat(np.arange(4), S // 4)[None, :].repeat(B, 0)
        .astype(np.int32))            # 4 packed segments per row

    def grads(fused, with_seg):
        blocks = {} if fused else {"block_q": 128, "block_k": 128}

        def f(q, k, v, bias):
            return jnp.sum(
                flash_attention(q, k, v, bias=bias,
                                segment_ids=seg if with_seg else None,
                                scale=scale, causal=True,
                                interpret=True, **blocks) * 0.01)

        return jax.grad(f, argnums=(0, 1, 2, 3))(q, k, v, bias)

    for with_seg in (False, True):
        gf = grads(True, with_seg)
        gt = grads(False, with_seg)
        for a, b, name in zip(gf, gt, ["q", "k", "v", "bias"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg="fused-bwd grad mismatch for %s (seg=%s)"
                        % (name, with_seg))


def test_explicit_block_override_changes_lowered_grid(monkeypatch):
    """block_q/block_k are a hard contract: an explicit override must
    actually change the pallas grid (the knob the autotuner searches),
    not silently fall back to the heuristic."""
    from paddle_tpu.ops.pallas import attention as A

    B, H, S, D = 1, 2, 512, 64
    q = _rand((B, H, S, D), 11)
    grids = []
    orig = A.pl.pallas_call

    def spy(*args, **kw):
        grids.append(kw.get("grid"))
        return orig(*args, **kw)

    monkeypatch.setattr(A.pl, "pallas_call", spy)
    A.flash_attention(q, q, q, interpret=True)
    default_grid = grids[-1]
    grids.clear()
    A.flash_attention(q, q, q, interpret=True, block_q=128, block_k=256)
    override_grid = grids[-1]
    assert default_grid == (B * H, 1, 1)          # heuristic: one 512 block
    assert override_grid == (B * H, 512 // 128, 512 // 256)
    assert override_grid != default_grid


def test_explicit_block_override_matches_naive_fwd_bwd():
    B, H, S, D = 1, 2, 256, 64
    q, k, v = _rand((B, H, S, D), 12), _rand((B, H, S, D), 13), \
        _rand((B, H, S, D), 14)
    scale = D ** -0.5

    def f(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, scale=scale, causal=True, interpret=True,
            block_q=128, block_k=128) * 0.01)

    def f_ref(q, k, v):
        return jnp.sum(_naive_attention(q, k, v, None, scale, True) * 0.01)

    out = flash_attention(q, k, v, scale=scale, causal=True,
                          interpret=True, block_q=128, block_k=128)
    ref = _naive_attention(q, k, v, None, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3,
            err_msg="block-override grad mismatch for %s" % name)


def test_explicit_block_invalid_raises():
    from paddle_tpu.ops.pallas.attention import _block_sizes

    B, H, S, D = 1, 1, 256, 64
    q = _rand((B, H, S, D), 15)
    # non-divisor: hard error, never a silent fallback
    with pytest.raises(ValueError, match="must divide"):
        flash_attention(q, q, q, interpret=True, block_q=100)
    with pytest.raises(ValueError, match="must divide"):
        _block_sizes(256, 200, 128, None)   # no block divides the other
    # explicit arguments beat the heuristic, which alone never raises
    assert _block_sizes(256, 256, 128, 128) == (128, 128)
    assert _block_sizes(256, 256) == (256, 256)
    assert _block_sizes(256, 200) == (256, None)


def test_partial_explicit_block_keeps_heuristic_for_other_side():
    from paddle_tpu.ops.pallas.attention import _block_sizes

    assert _block_sizes(512, 512, 128, None) == (128, 512)
    assert _block_sizes(512, 768, None, 128) == (512, 128)


def test_dispatch_choice_is_counted_with_the_rule_that_decided():
    """No dispatch between a kernel and its composition is silent: each
    trace lands in `kernel_dispatch_total{op, impl, rule}`."""
    import jax.numpy as jnp

    from paddle_tpu.fluid.core.registry import LowerContext, get_op_def
    from paddle_tpu.ops import dispatch
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    from paddle_tpu.ops.pallas.matmul import matmul_bias_act

    before = dispatch.choices()

    def delta(key):
        return dispatch.choices().get(key, 0) - before.get(key, 0)

    q = jnp.ones((1, 2, 8, 16), jnp.float32)
    scaled_dot_product_attention(q, q, q)
    assert delta(("attention", "naive", "backend is not a TPU")) == 1

    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 8), jnp.float32)
    get_op_def("matmul_bias_act").lower(
        LowerContext(), {"X": [x], "Y": [w], "Bias": [jnp.ones((8,))]},
        {"act_type": "relu", "x_num_col_dims": 1})
    key = ("matmul_bias_act", "xla composition", "backend is not a TPU")
    assert delta(key) == 1
    # the kernel called directly is the caller's choice, not a dispatch
    matmul_bias_act(jnp.ones((128, 128)), jnp.ones((128, 128)),
                    jnp.ones((128,)), interpret=True)
    assert delta(key) == 1 and not any(
        k[0] == "matmul_bias_act" and k != key and delta(k)
        for k in dispatch.choices())


def test_dispatch_names_the_shape_rule_on_a_tpu(monkeypatch):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import _naive_reason

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((48, 512, 12, 64), jnp.bfloat16)
    assert _naive_reason(q, q, None, "BSHD") is None
    short = jax.ShapeDtypeStruct((1, 8, 12, 64), jnp.float32)
    assert "shorter than 192" in _naive_reason(short, short, None, "BSHD")


def test_no_environment_switch_picks_code_on_the_hot_paths():
    """Kernels, models and the generation engine take their choices
    from arguments and from what they can observe (the backend, the
    shapes), never from the environment.  The one exception is named
    debt: `PADDLE_TPU_GEMM_BLOCKS` in `ops/pallas/matmul.py`, on no
    benchmark cell's path (ROADMAP D5)."""
    import os
    import re

    import paddle_tpu

    root = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
    reads = re.compile(r"os\.(environ|getenv|putenv)|from os import")
    found = {}
    for sub in ("ops", "models", "generation"):
        for folder, _, files in os.walk(os.path.join(root, sub)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(folder, name)
                with open(path) as f:
                    lines = [ln.strip() for ln in f if reads.search(ln)]
                if lines:
                    found[os.path.relpath(path, root)] = lines
    assert set(found) <= {os.path.join("ops", "pallas", "matmul.py")}, found
    assert all("PADDLE_TPU_GEMM_BLOCKS" in ln
               for lines in found.values() for ln in lines), found
