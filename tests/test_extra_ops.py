"""OpTest oracles for the round-2 breadth op families (linalg_ops.py,
extra_ops.py) — outputs vs numpy/scipy, finite-difference grads for a
representative sample (reference tests/unittests/test_*_op.py pattern)."""

import numpy as np
import pytest
import scipy.special

from op_test import check_grad, check_output, run_single_op

rng = np.random.RandomState(7)


def _r(*shape):
    return rng.randn(*shape).astype(np.float32)


# ---------------------------------------------------------------------------
# unary activations / math
# ---------------------------------------------------------------------------

UNARY_CASES = [
    ("sinh", np.sinh, _r(3, 4), {}),
    ("cosh", np.cosh, _r(3, 4), {}),
    ("tan", np.tan, _r(3, 4) * 0.5, {}),
    ("asin", np.arcsin, _r(3, 4) * 0.5, {}),
    ("acos", np.arccos, _r(3, 4) * 0.5, {}),
    ("atan", np.arctan, _r(3, 4), {}),
    ("asinh", np.arcsinh, _r(3, 4), {}),
    ("acosh", np.arccosh, np.abs(_r(3, 4)) + 1.5, {}),
    ("atanh", np.arctanh, _r(3, 4) * 0.5, {}),
    ("expm1", np.expm1, _r(3, 4), {}),
    ("log1p", np.log1p, np.abs(_r(3, 4)), {}),
    ("log2", np.log2, np.abs(_r(3, 4)) + 0.1, {}),
    ("log10", np.log10, np.abs(_r(3, 4)) + 0.1, {}),
    ("lgamma", scipy.special.gammaln, np.abs(_r(3, 4)) + 0.5, {}),
    ("digamma", scipy.special.digamma, np.abs(_r(3, 4)) + 0.5, {}),
    ("erfinv", scipy.special.erfinv, _r(3, 4) * 0.5, {}),
    ("trunc", np.trunc, _r(3, 4) * 3, {}),
    ("frac", lambda x: x - np.trunc(x), _r(3, 4) * 3, {}),
    ("tanh_shrink", lambda x: x - np.tanh(x), _r(3, 4), {}),
    ("hard_shrink", lambda x: np.where(np.abs(x) > 0.5, x, 0), _r(3, 4), {}),
    ("softshrink",
     lambda x: np.sign(x) * np.maximum(np.abs(x) - 0.5, 0), _r(3, 4), {}),
    ("thresholded_relu", lambda x: np.where(x > 1.0, x, 0), _r(3, 4) * 2, {}),
    ("stanh", lambda x: 1.7159 * np.tanh(0.67 * x), _r(3, 4), {}),
    ("mish",
     lambda x: x * np.tanh(np.log1p(np.exp(-np.abs(x)))
                           + np.maximum(x, 0)), _r(3, 4), {}),
    ("selu",
     lambda x: 1.0507009873554805 * np.where(
         x > 0, x, 1.6732632423543772 * (np.exp(x) - 1)), _r(3, 4), {}),
    ("erfc", scipy.special.erfc, _r(3, 4), {}),
    ("hard_swish",
     lambda x: x * np.clip(x / 6.0 + 0.5, 0, 1), _r(3, 4) * 4, {}),
]


@pytest.mark.parametrize(
    "op,ref,x,attrs", UNARY_CASES, ids=[c[0] for c in UNARY_CASES]
)
def test_unary_op(op, ref, x, attrs):
    check_output(op, {"X": x}, attrs, {"Out": ref(x)}, rtol=2e-5, atol=2e-5)


def test_unary_grads_sample():
    for op, x in [("sinh", _r(2, 3)), ("log1p", np.abs(_r(2, 3)) + 0.2),
                  ("mish", _r(2, 3))]:
        check_grad(op, {"X": x}, {}, ["Out"], ["X"])


def test_atan2_logsumexp_cumprod():
    x, y = _r(3, 4), np.abs(_r(3, 4)) + 0.1
    check_output("atan2", {"X1": x, "X2": y}, {},
                 {"Out": np.arctan2(x, y)})
    check_output("logsumexp", {"X": x}, {"axis": [1], "keepdim": False},
                 {"Out": scipy.special.logsumexp(x, axis=1)}, rtol=1e-5)
    check_output("cumprod", {"X": x}, {"dim": 1},
                 {"Out": np.cumprod(x, axis=1)}, rtol=1e-5)


# ---------------------------------------------------------------------------
# linalg
# ---------------------------------------------------------------------------


def test_kron_einsum_multidot():
    a, b = _r(2, 3), _r(4, 5)
    check_output("kron", {"X": a, "Y": b}, {}, {"Out": np.kron(a, b)})
    x, y = _r(3, 4), _r(4, 5)
    check_output("einsum", {"Operands": [x, y]}, {"equation": "ij,jk->ik"},
                 {"Out": x @ y}, rtol=1e-4)
    z = _r(5, 2)
    check_output("multi_dot", {"X": [x, y, z]}, {},
                 {"Out": x @ y @ z}, rtol=1e-4)


def test_cholesky_inverse_matrix_power_triangular_solve():
    a = _r(4, 4)
    spd = a @ a.T + 4 * np.eye(4, dtype=np.float32)
    check_output("cholesky", {"X": spd}, {},
                 {"Out": np.linalg.cholesky(spd)}, rtol=1e-4, atol=1e-4)
    check_output("inverse", {"Input": spd}, {},
                 {"Output": np.linalg.inv(spd)}, rtol=1e-3, atol=1e-4)
    check_output("matrix_power", {"X": spd}, {"n": 3},
                 {"Out": np.linalg.matrix_power(spd, 3)}, rtol=1e-3)
    L = np.tril(a) + 4 * np.eye(4, dtype=np.float32)
    b = _r(4, 2)
    check_output(
        "triangular_solve", {"X": L, "Y": b},
        {"upper": False},
        {"Out": scipy.linalg.solve_triangular(L, b, lower=True)},
        rtol=1e-4, atol=1e-5,
    )


def test_cross_trace_diag():
    x, y = _r(4, 3), _r(4, 3)
    check_output("cross", {"X": x, "Y": y}, {"dim": 1},
                 {"Out": np.cross(x, y, axis=1)}, rtol=1e-5)
    m = _r(4, 4)
    check_output("trace", {"Input": m}, {}, {"Out": np.trace(m)}, rtol=1e-5)
    v = _r(5)
    check_output("diag_v2", {"X": v}, {"offset": 1},
                 {"Out": np.diag(v, k=1)})


def test_diag_embed():
    x = _r(2, 3)
    want = np.zeros((2, 3, 3), np.float32)
    for i in range(2):
        want[i] = np.diag(x[i])
    check_output("diag_embed", {"Input": x}, {}, {"Out": want})


def test_dist_histogram_bincount_index_sample():
    x, y = _r(3, 4), _r(3, 4)
    check_output("dist", {"X": x, "Y": y}, {"p": 2.0},
                 {"Out": np.linalg.norm((x - y).reshape(-1))}, rtol=1e-5)
    ints = rng.randint(0, 10, (20,)).astype(np.int64)
    want = np.bincount(ints, minlength=10)
    check_output("bincount", {"X": ints}, {"minlength": 10}, {"Out": want})
    xi = _r(3, 5)
    idx = rng.randint(0, 5, (3, 2)).astype(np.int64)
    check_output("index_sample", {"X": xi, "Index": idx}, {},
                 {"Out": np.take_along_axis(xi, idx, axis=1)})


# ---------------------------------------------------------------------------
# manipulation
# ---------------------------------------------------------------------------


def test_manipulation_ops():
    x = _r(3, 4)
    check_output("roll", {"X": x}, {"shifts": [1], "axis": [0]},
                 {"Out": np.roll(x, 1, 0)})
    check_output("flip", {"X": x}, {"axis": [1]}, {"Out": np.flip(x, 1)})
    b = _r(1, 4)
    check_output("broadcast_to", {"X": b}, {"shape": [3, 4]},
                 {"Out": np.broadcast_to(b, (3, 4))})
    check_output("repeat_interleave", {"X": x}, {"repeats": 2, "dim": 1},
                 {"Out": np.repeat(x, 2, axis=1)})
    idx = rng.randint(0, 3, (3, 4)).astype(np.int64)
    check_output("take_along_axis", {"Input": x, "Index": idx}, {"Axis": 0},
                 {"Result": np.take_along_axis(x, idx, 0)})


def test_put_along_axis_and_scatter_nd_add():
    x = _r(3, 4)
    idx = rng.randint(0, 3, (2, 4)).astype(np.int64)
    v = _r(2, 4)
    want = x.copy()
    np.put_along_axis(want, idx, v, axis=0)
    # duplicate indices: last-write-wins differs between impls; use unique
    idx = np.stack([np.random.RandomState(1).permutation(3)[:2]
                    for _ in range(4)], axis=1).astype(np.int64)
    want = x.copy()
    np.put_along_axis(want, idx, v, axis=0)
    check_output("put_along_axis",
                 {"Input": x, "Index": idx, "Value": v},
                 {"Axis": 0, "Reduce": "assign"}, {"Result": want})

    base = _r(5, 3)
    sidx = np.array([[0], [2], [4]], np.int64)
    upd = _r(3, 3)
    want2 = base.copy()
    for i, r in enumerate(sidx[:, 0]):
        want2[r] += upd[i]
    check_output("scatter_nd_add", {"X": base, "Index": sidx, "Updates": upd},
                 {}, {"Out": want2}, rtol=1e-5)


def test_unfold_matches_manual_im2col():
    x = _r(2, 3, 6, 6)
    outs, _ = run_single_op(
        "unfold", {"X": x},
        {"kernel_sizes": [2, 2], "strides": [2, 2], "paddings": [0, 0],
         "dilations": [1, 1]},
        ["Y"],
    )
    got = outs["Y"]
    assert got.shape == (2, 3 * 4, 9)
    # spot-check one patch: output column 0 = patch at (0,0)
    patch = x[:, :, 0:2, 0:2].reshape(2, 3, 4)
    np.testing.assert_allclose(
        got[:, :, 0].reshape(2, 3, 4), patch, rtol=1e-6
    )


def test_sort_searchsorted_kthvalue_shard_index():
    x = _r(3, 5)
    outs, _ = run_single_op("sort", {"X": x}, {"axis": 1}, ["Out", "Indices"])
    np.testing.assert_allclose(outs["Out"], np.sort(x, 1), rtol=1e-6)
    seq = np.sort(_r(6))
    vals = _r(4)
    check_output("searchsorted", {"SortedSequence": seq, "Values": vals}, {},
                 {"Out": np.searchsorted(seq, vals)})
    outs, _ = run_single_op("kthvalue", {"X": x}, {"k": 2, "axis": 1},
                            ["Out", "Indices"])
    np.testing.assert_allclose(outs["Out"], np.sort(x, 1)[:, 1], rtol=1e-6)
    ids = np.arange(20).astype(np.int64)
    outs, _ = run_single_op(
        "shard_index", {"X": ids},
        {"index_num": 20, "nshards": 2, "shard_id": 1, "ignore_value": -1},
        ["Out"],
    )
    want = np.where(ids // 10 == 1, ids % 10, -1)
    np.testing.assert_array_equal(outs["Out"], want)


def test_meshgrid():
    a, b = _r(3), _r(4)
    outs, _ = run_single_op("meshgrid", {"X": [a, b]}, {}, ["Out"])
    # first output only via harness; check shape + content through numpy
    ga, gb = np.meshgrid(a, b, indexing="ij")
    np.testing.assert_allclose(outs["Out"], ga, rtol=1e-6)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def test_loss_ops():
    logp = np.log(scipy.special.softmax(_r(4, 5), axis=1))
    tgt = scipy.special.softmax(_r(4, 5), axis=1)
    want = np.mean(tgt * (np.log(np.maximum(tgt, 1e-10)) - logp))
    check_output("kldiv_loss", {"X": logp, "Target": tgt},
                 {"reduction": "mean"}, {"Loss": want}, rtol=1e-4)

    p = np.clip(np.abs(_r(4, 1)), 0.05, 0.95)
    l = (rng.rand(4, 1) > 0.5).astype(np.float32)
    want = -l * np.log(p + 1e-4) - (1 - l) * np.log(1 - p + 1e-4)
    check_output("log_loss", {"Predicted": p, "Labels": l},
                 {"epsilon": 1e-4}, {"Loss": want}, rtol=1e-5)

    onehot = np.eye(5, dtype=np.float32)[rng.randint(0, 5, 4)]
    check_output("label_smooth", {"X": onehot}, {"epsilon": 0.1},
                 {"Out": 0.9 * onehot + 0.1 / 5}, rtol=1e-5)

    x1, x2 = _r(4, 1), _r(4, 1)
    lab = np.sign(_r(4, 1)).astype(np.float32)
    check_output("margin_rank_loss", {"X1": x1, "X2": x2, "Label": lab},
                 {"margin": 0.1},
                 {"Out": np.maximum(0, -lab * (x1 - x2) + 0.1)}, rtol=1e-5)

    logits = _r(4, 1)
    blab = (rng.rand(4, 1) > 0.5).astype(np.float32)
    check_output("hinge_loss", {"Logits": logits, "Labels": blab}, {},
                 {"Loss": np.maximum(0, 1 - (2 * blab - 1) * logits)},
                 rtol=1e-5)

    a, b = _r(4, 8), _r(4, 8)
    cs = np.sum(a * b, -1, keepdims=True) / (
        np.linalg.norm(a, axis=-1, keepdims=True)
        * np.linalg.norm(b, axis=-1, keepdims=True) + 1e-12
    )
    check_output("cos_sim", {"X": a, "Y": b}, {}, {"Out": cs}, rtol=1e-4)

    x = np.log(scipy.special.softmax(_r(6, 4), axis=1))
    lbl = rng.randint(0, 4, (6,)).astype(np.int64)
    picked = -x[np.arange(6), lbl]
    check_output("nll_loss", {"X": x, "Label": lbl}, {"reduction": "mean"},
                 {"Out": picked.mean()}, rtol=1e-5)

    pr = np.clip(np.abs(_r(4, 1)), 0.05, 0.95)
    check_output("bce_loss", {"X": pr, "Label": blab}, {},
                 {"Out": -(blab * np.log(pr) + (1 - blab) * np.log(1 - pr))},
                 rtol=1e-4)

    d = _r(4, 3)
    y = _r(4, 3)
    diff = d - y
    sl1 = np.where(np.abs(diff) < 1.0, 0.5 * diff**2, np.abs(diff) - 0.5)
    outs, _ = run_single_op("smooth_l1_loss", {"X": d, "Y": y}, {"sigma": 1.0},
                            ["Out", "Diff"])
    np.testing.assert_allclose(outs["Out"], sl1, rtol=1e-5)


def test_loss_grads_sample():
    p = np.clip(np.abs(_r(3, 1)), 0.1, 0.9)
    l = (rng.rand(3, 1) > 0.5).astype(np.float32)
    check_grad("bce_loss", {"X": p, "Label": l}, {}, ["Out"], ["X"])
    x, y = _r(3, 4), _r(3, 4)
    check_grad("cos_sim", {"X": x, "Y": y}, {}, ["Out"], ["X", "Y"])


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def test_instance_norm():
    x = _r(2, 3, 4, 4)
    scale = np.abs(_r(3)) + 0.5
    bias = _r(3)
    mean = x.mean(axis=(2, 3), keepdims=True)
    var = x.var(axis=(2, 3), keepdims=True)
    want = (x - mean) / np.sqrt(var + 1e-5)
    want = want * scale.reshape(1, 3, 1, 1) + bias.reshape(1, 3, 1, 1)
    outs, _ = run_single_op(
        "instance_norm", {"X": x, "Scale": scale, "Bias": bias},
        {"epsilon": 1e-5}, ["Y"],
    )
    np.testing.assert_allclose(outs["Y"], want, rtol=1e-4, atol=1e-5)


def test_spectral_norm():
    w = _r(6, 4)
    u = _r(6)
    v = _r(4)
    outs, _ = run_single_op(
        "spectral_norm", {"Weight": w, "U": u, "V": v},
        {"dim": 0, "power_iters": 20}, ["Out"],
    )
    sigma = np.linalg.svd(w, compute_uv=False)[0]
    np.testing.assert_allclose(outs["Out"], w / sigma, rtol=1e-3, atol=1e-4)


def test_sync_batch_norm_single_rank_matches_bn():
    x = _r(4, 3, 2, 2)
    scale = np.abs(_r(3)) + 0.5
    bias = _r(3)
    rm = np.zeros(3, np.float32)
    rv = np.ones(3, np.float32)
    outs, _ = run_single_op(
        "sync_batch_norm",
        {"X": x, "Scale": scale, "Bias": bias, "Mean": rm, "Variance": rv},
        {"epsilon": 1e-5, "momentum": 0.9},
        ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
    )
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    want = (x - mean.reshape(1, 3, 1, 1)) / np.sqrt(
        var.reshape(1, 3, 1, 1) + 1e-5
    ) * scale.reshape(1, 3, 1, 1) + bias.reshape(1, 3, 1, 1)
    np.testing.assert_allclose(outs["Y"], want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs["SavedMean"], mean, rtol=1e-5)


def test_sync_batch_norm_syncs_across_mesh_ranks():
    """The defining property: with per-rank different shards, normalization
    uses the GLOBAL batch statistics (cf. sync_batch_norm_op.cu)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.fluid.core.registry import get_op_def, LowerContext
    from paddle_tpu import distributed as dist

    mesh = dist.auto_mesh(8)
    x = _r(16, 3, 2, 2)
    scale = np.abs(_r(3)) + 0.5
    bias = _r(3)
    rm, rv = np.zeros(3, np.float32), np.ones(3, np.float32)
    opdef = get_op_def("sync_batch_norm")

    def body(xs):
        out = opdef.lower(
            LowerContext(),
            {"X": [xs], "Scale": [jnp.asarray(scale)],
             "Bias": [jnp.asarray(bias)], "Mean": [jnp.asarray(rm)],
             "Variance": [jnp.asarray(rv)]},
            {"epsilon": 1e-5},
        )
        return out["Y"][0]

    y = jax.jit(jax.shard_map(
        body, mesh=mesh.mesh,
        in_specs=(P("dp"),), out_specs=P("dp"), check_vma=False,
    ))(x)
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    want = (x - mean.reshape(1, 3, 1, 1)) / np.sqrt(
        var.reshape(1, 3, 1, 1) + 1e-5
    ) * scale.reshape(1, 3, 1, 1) + bias.reshape(1, 3, 1, 1)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# vision
# ---------------------------------------------------------------------------


def test_affine_grid_identity():
    theta = np.tile(
        np.array([[1, 0, 0], [0, 1, 0]], np.float32)[None], (2, 1, 1)
    )
    outs, _ = run_single_op(
        "affine_grid", {"Theta": theta},
        {"output_shape": [2, 3, 4, 5], "align_corners": True}, ["Output"],
    )
    g = outs["Output"]
    assert g.shape == (2, 4, 5, 2)
    np.testing.assert_allclose(g[0, 0, 0], [-1, -1], atol=1e-6)
    np.testing.assert_allclose(g[0, -1, -1], [1, 1], atol=1e-6)


def test_grid_sampler_identity_grid_reproduces_input():
    x = _r(2, 3, 5, 5)
    ys = np.linspace(-1, 1, 5, dtype=np.float32)
    xs = np.linspace(-1, 1, 5, dtype=np.float32)
    gy, gx = np.meshgrid(ys, xs, indexing="ij")
    grid = np.tile(np.stack([gx, gy], -1)[None], (2, 1, 1, 1))
    outs, _ = run_single_op(
        "grid_sampler", {"X": x, "Grid": grid}, {"align_corners": True},
        ["Output"],
    )
    np.testing.assert_allclose(outs["Output"], x, rtol=1e-4, atol=1e-5)


def test_interp_and_pixel_shuffle():
    x = _r(1, 2, 4, 4)
    outs, _ = run_single_op(
        "nearest_interp", {"X": x}, {"out_h": 8, "out_w": 8}, ["Out"]
    )
    assert outs["Out"].shape == (1, 2, 8, 8)
    np.testing.assert_allclose(outs["Out"][:, :, ::2, ::2], x, rtol=1e-5)

    outs, _ = run_single_op(
        "bilinear_interp", {"X": x},
        {"out_h": 7, "out_w": 7, "align_corners": True}, ["Out"]
    )
    assert outs["Out"].shape == (1, 2, 7, 7)
    # corner alignment: corners exactly preserved
    np.testing.assert_allclose(outs["Out"][:, :, 0, 0], x[:, :, 0, 0],
                               rtol=1e-5)
    np.testing.assert_allclose(outs["Out"][:, :, -1, -1], x[:, :, -1, -1],
                               rtol=1e-5)

    ps = _r(1, 8, 3, 3)
    outs, _ = run_single_op(
        "pixel_shuffle", {"X": ps}, {"upscale_factor": 2}, ["Out"]
    )
    assert outs["Out"].shape == (1, 2, 6, 6)
    np.testing.assert_allclose(outs["Out"][0, 0, 0, 0], ps[0, 0, 0, 0])


def test_conv3d_pool3d():
    x = _r(1, 2, 4, 4, 4)
    f = _r(3, 2, 2, 2, 2)
    outs, _ = run_single_op(
        "conv3d", {"Input": x, "Filter": f},
        {"strides": [1, 1, 1], "paddings": [0, 0, 0], "dilations": [1, 1, 1]},
        ["Output"],
    )
    assert outs["Output"].shape == (1, 3, 3, 3, 3)
    # oracle at one position
    want = np.sum(x[0, :, 0:2, 0:2, 0:2] * f[0])
    np.testing.assert_allclose(outs["Output"][0, 0, 0, 0, 0], want,
                               rtol=1e-4)

    outs, _ = run_single_op(
        "pool3d", {"X": x},
        {"ksize": [2, 2, 2], "strides": [2, 2, 2], "paddings": [0, 0, 0],
         "pooling_type": "max"},
        ["Out"],
    )
    assert outs["Out"].shape == (1, 2, 2, 2, 2)
    np.testing.assert_allclose(
        outs["Out"][0, 0, 0, 0, 0], x[0, 0, :2, :2, :2].max(), rtol=1e-6
    )
