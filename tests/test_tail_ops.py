"""Round-4 op-tail oracles (reference tests/unittests/test_*_op.py
patterns): numpy value checks + finite-difference grads for the
differentiable ops."""

import numpy as np
import pytest

from op_test import check_grad, check_output, run_single_op


def _rand(*shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


# --- math / tensor ---------------------------------------------------------


def test_tril_triu():
    x = _rand(4, 5)
    check_output("tril_triu", {"X": x}, {"lower": True, "diagonal": 1},
                 {"Out": np.tril(x, 1)})
    check_output("tril_triu", {"X": x}, {"lower": False, "diagonal": -1},
                 {"Out": np.triu(x, -1)})
    check_grad("tril_triu", {"X": x}, {"lower": True}, ["Out"], ["X"],
               rtol=1e-2, atol=1e-3)


def test_multiplex():
    xs = [_rand(4, 3, seed=i) for i in range(3)]
    ids = np.array([[2], [0], [1], [0]], np.int32)
    ref = np.stack([xs[ids[i, 0]][i] for i in range(4)])
    check_output("multiplex", {"X": xs, "Ids": ids}, {}, {"Out": ref})


def test_minus_and_reverse():
    x, y = _rand(3, 4), _rand(3, 4, seed=1)
    check_output("minus", {"X": x, "Y": y}, {}, {"Out": x - y})
    check_output("reverse", {"X": x}, {"axis": [1]},
                 {"Out": x[:, ::-1]})
    check_grad("reverse", {"X": x}, {"axis": [0, 1]}, ["Out"], ["X"],
               rtol=1e-2, atol=1e-3)


def test_eye_diag_fill():
    outs, _ = run_single_op("eye", {}, {"num_rows": 3, "num_columns": 4},
                            ["Out"])
    np.testing.assert_allclose(outs["Out"], np.eye(3, 4))
    d = _rand(5)
    outs, _ = run_single_op("diag", {"Diagonal": d}, {}, ["Out"])
    np.testing.assert_allclose(outs["Out"], np.diag(d), rtol=1e-6)
    outs, _ = run_single_op(
        "fill", {}, {"shape": [2, 3], "value": [1, 2, 3, 4, 5, 6],
                     "dtype": "float32"}, ["Out"])
    np.testing.assert_allclose(outs["Out"],
                               np.arange(1, 7).reshape(2, 3))


def test_fill_zeros_like2_and_range():
    x = _rand(2, 3)
    outs, _ = run_single_op("fill_zeros_like2", {"X": x},
                            {"dtype": "float32"}, ["Out"])
    assert (outs["Out"] == 0).all() and outs["Out"].shape == (2, 3)
    outs, _ = run_single_op("range", {}, {"start": 1, "end": 8, "step": 2},
                            ["Out"])
    np.testing.assert_allclose(outs["Out"], np.arange(1, 8, 2))


def test_unique_and_counts():
    x = np.array([3, 1, 3, 2, 1, 7], np.int64)
    outs, _ = run_single_op("unique", {"X": x}, {}, ["Out", "Index"])
    uniq = np.unique(x)
    np.testing.assert_allclose(outs["Out"][: len(uniq)], uniq)
    np.testing.assert_allclose(uniq[outs["Index"]], x)
    outs, _ = run_single_op("unique_with_counts", {"X": x}, {},
                            ["Out", "Index", "Count"])
    np.testing.assert_allclose(outs["Count"][: len(uniq)],
                               [2, 1, 2, 1])


def test_where_index_and_is_empty():
    c = np.array([[True, False], [False, True]])
    outs, _ = run_single_op("where_index", {"Condition": c}, {}, ["Out"])
    got = outs["Out"]
    np.testing.assert_allclose(got[:2], [[0, 0], [1, 1]])
    assert (got[2:] == -1).all()
    outs, _ = run_single_op("is_empty", {"X": np.zeros((2, 2))}, {},
                            ["Out"])
    assert not bool(outs["Out"])


def test_gaussian_random_batch_size_like_shape():
    outs, _ = run_single_op(
        "gaussian_random_batch_size_like", {"Input": _rand(6, 3)},
        {"shape": [99, 7], "input_dim_idx": 0, "output_dim_idx": 0,
         "mean": 10.0, "std": 0.1}, ["Out"])
    assert outs["Out"].shape == (6, 7)
    assert 9 < outs["Out"].mean() < 11


def test_bilinear_tensor_product():
    x, y = _rand(3, 4), _rand(3, 5, seed=1)
    w = _rand(2, 4, 5, seed=2)
    b = _rand(1, 2, seed=3)
    ref = np.einsum("bm,omn,bn->bo", x, w, y) + b
    check_output("bilinear_tensor_product",
                 {"X": x, "Y": y, "Weight": w, "Bias": b}, {},
                 {"Out": ref}, rtol=1e-5, atol=1e-5)
    check_grad("bilinear_tensor_product",
               {"X": x, "Y": y, "Weight": w, "Bias": b}, {}, ["Out"],
               ["X", "Weight"], rtol=1e-2, atol=1e-2)


def test_cross_entropy2():
    p = np.abs(_rand(4, 5)) + 0.1
    p = (p / p.sum(1, keepdims=True)).astype(np.float32)
    lab = np.array([[1], [0], [4], [2]], np.int64)
    ref = -np.log(p[np.arange(4), lab[:, 0]])[:, None]
    check_output("cross_entropy2", {"X": p, "Label": lab}, {},
                 {"Y": ref}, rtol=1e-5, atol=1e-6)


def test_conv_shift():
    x, y = _rand(2, 6), _rand(2, 3, seed=1)
    M, N = 6, 3
    ref = np.zeros((2, M), np.float32)
    for b in range(2):
        for i in range(M):
            for j in range(N):
                ref[b, i] += x[b, (i + j - N // 2) % M] * y[b, j]
    check_output("conv_shift", {"X": x, "Y": y}, {}, {"Out": ref},
                 rtol=1e-5, atol=1e-5)
    check_grad("conv_shift", {"X": x, "Y": y}, {}, ["Out"], ["X", "Y"],
               rtol=1e-2, atol=1e-3)


def test_bpr_loss():
    x = _rand(3, 4)
    lab = np.array([[0], [2], [3]], np.int64)
    ref = np.zeros((3, 1), np.float32)
    for b in range(3):
        pos = x[b, lab[b, 0]]
        o = [np.log(1 + np.exp(-(pos - x[b, j])))
             for j in range(4) if j != lab[b, 0]]
        ref[b, 0] = np.mean(o)
    check_output("bpr_loss", {"X": x, "Label": lab}, {}, {"Out": ref},
                 rtol=1e-5, atol=1e-5)
    check_grad("bpr_loss", {"X": x, "Label": lab}, {}, ["Out"], ["X"],
               rtol=1e-2, atol=1e-3)


def test_cvm():
    x = np.abs(_rand(3, 6)) + 0.5
    outs, _ = run_single_op("cvm", {"X": x, "CVM": x[:, :2]},
                            {"use_cvm": True}, ["Y"])
    np.testing.assert_allclose(outs["Y"][:, 0], np.log(x[:, 0] + 1),
                               rtol=1e-5)
    np.testing.assert_allclose(
        outs["Y"][:, 1], np.log(x[:, 1] + 1) - np.log(x[:, 0] + 1),
        rtol=1e-4, atol=1e-5)
    outs, _ = run_single_op("cvm", {"X": x, "CVM": x[:, :2]},
                            {"use_cvm": False}, ["Y"])
    np.testing.assert_allclose(outs["Y"], x[:, 2:], rtol=1e-6)


def test_hash_deterministic_in_range():
    x = np.array([[1, 2], [3, 4], [1, 2]], np.int64)
    outs, _ = run_single_op("hash", {"X": x},
                            {"num_hash": 2, "mod_by": 1000}, ["Out"])
    got = outs["Out"]
    assert got.shape == (3, 2, 1)
    assert (got >= 0).all() and (got < 1000).all()
    np.testing.assert_array_equal(got[0], got[2])  # same input, same hash
    assert (got[0] != got[1]).any()


def test_average_accumulates_window():
    p = _rand(3)
    z = np.zeros(3, np.float32)
    zi = np.zeros((1,), np.int64)
    ins = {"param": p, "in_sum_1": z, "in_sum_2": z, "in_sum_3": z,
           "in_num_accumulates": zi, "in_old_num_accumulates": zi,
           "in_num_updates": zi}
    outs, _ = run_single_op(
        "average_accumulates", ins,
        {"average_window": 1.0, "min_average_window": 1,
         "max_average_window": 100},
        ["out_sum_1", "out_sum_3", "out_num_accumulates",
         "out_old_num_accumulates"])
    # window closes on the first update: sum_3 = param, accumulators reset
    np.testing.assert_allclose(outs["out_sum_3"], p, rtol=1e-6)
    assert int(outs["out_num_accumulates"][0]) == 0
    assert int(outs["out_old_num_accumulates"][0]) == 1


def test_proximal_updates():
    p, g, m = _rand(4), _rand(4, seed=1), np.abs(_rand(4, seed=2)) + 0.1
    lr = np.array([0.1], np.float32)
    outs, _ = run_single_op(
        "proximal_gd", {"Param": p, "Grad": g, "LearningRate": lr},
        {"l1": 0.01, "l2": 0.02}, ["ParamOut"])
    prox = p - 0.1 * g
    ref = np.sign(prox) * np.maximum(np.abs(prox) - 0.1 * 0.01, 0) \
        / (1 + 0.1 * 0.02)
    np.testing.assert_allclose(outs["ParamOut"], ref, rtol=1e-5, atol=1e-6)
    outs, _ = run_single_op(
        "proximal_adagrad",
        {"Param": p, "Moment": m, "Grad": g, "LearningRate": lr},
        {"l1": 0.01, "l2": 0.02}, ["ParamOut", "MomentOut"])
    m2 = m + g * g
    lr_adj = 0.1 / np.sqrt(m2)
    prox = p - lr_adj * g
    ref = np.sign(prox) * np.maximum(np.abs(prox) - lr_adj * 0.01, 0) \
        / (1 + lr_adj * 0.02)
    np.testing.assert_allclose(outs["MomentOut"], m2, rtol=1e-5)
    np.testing.assert_allclose(outs["ParamOut"], ref, rtol=1e-4, atol=1e-5)


def test_selected_rows_helpers_and_misc():
    v = _rand(4, 3)
    ids = np.array([5, 2, 5, 9], np.int64)
    outs, _ = run_single_op("merge_selected_rows",
                            {"X": v, "RowIds": ids}, {}, ["Out"])
    ref = v.copy()
    ref[0] = v[0] + v[2]
    ref[2] = 0
    np.testing.assert_allclose(outs["Out"], ref, rtol=1e-6)
    outs, _ = run_single_op("get_tensor_from_selected_rows", {"X": v}, {},
                            ["Out"])
    np.testing.assert_allclose(outs["Out"], v)
    outs, _ = run_single_op("fake_init", {}, {"shape": [2, 2]}, ["Out"])
    assert (outs["Out"] == 0).all()
    outs, _ = run_single_op("seed", {}, {"seed": 42}, ["Out"])
    assert int(outs["Out"][0]) == 42
    outs, _ = run_single_op("broadcast", {"X": v}, {}, ["Out"])
    np.testing.assert_allclose(outs["Out"], v)


# --- nn tail ---------------------------------------------------------------


def test_conv3d_transpose():
    import torch
    import torch.nn.functional as F

    x = _rand(1, 2, 3, 4, 4)
    w = _rand(2, 3, 2, 2, 2, seed=1)
    ref = F.conv_transpose3d(torch.from_numpy(x), torch.from_numpy(w),
                             stride=2, padding=1).numpy()
    check_output("conv3d_transpose", {"Input": x, "Filter": w},
                 {"strides": [2, 2, 2], "paddings": [1, 1, 1]},
                 {"Output": ref}, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_max_pool2d_with_index_and_unpool():
    x = _rand(2, 3, 4, 4)
    outs, _ = run_single_op(
        "max_pool2d_with_index", {"X": x},
        {"ksize": [2, 2], "strides": [2, 2]}, ["Out", "Mask"])
    ref = x.reshape(2, 3, 2, 2, 2, 2).max(axis=(3, 5))
    np.testing.assert_allclose(outs["Out"], ref, rtol=1e-6)
    # mask points at the argmax (flat in-plane index)
    flat = x.reshape(2, 3, 16)
    np.testing.assert_allclose(
        np.take_along_axis(flat, outs["Mask"].reshape(2, 3, 4), 2),
        ref.reshape(2, 3, 4), rtol=1e-6)
    # unpool round-trip: scatter pooled values back
    outs2, _ = run_single_op(
        "unpool", {"X": outs["Out"], "Indices": outs["Mask"]},
        {"unpooled_shape": [4, 4]}, ["Out"])
    up = outs2["Out"]
    np.testing.assert_allclose(up.reshape(2, 3, 16).sum(-1),
                               ref.reshape(2, 3, 4).sum(-1), rtol=1e-5)
    check_grad("max_pool2d_with_index", {"X": x},
               {"ksize": [2, 2], "strides": [2, 2]}, ["Out"], ["X"],
               rtol=1e-2, atol=1e-3)


def test_unpool_overlapping_windows_writes_not_sums():
    """ADVICE r4: stride < ksize lets two pooled cells record the SAME
    max index; the scatter must overwrite (reference single write), not
    sum the duplicates."""
    # one dominant peak: every overlapping window picks index 5 (=[1,1])
    x = np.zeros((1, 1, 3, 3), np.float32)
    x[0, 0, 1, 1] = 7.0
    outs, _ = run_single_op(
        "max_pool2d_with_index", {"X": x},
        {"ksize": [2, 2], "strides": [1, 1]}, ["Out", "Mask"])
    assert (outs["Mask"] == 4).all()          # all 4 windows hit (1,1)
    outs2, _ = run_single_op(
        "unpool", {"X": outs["Out"], "Indices": outs["Mask"]},
        {"unpooled_shape": [3, 3]}, ["Out"])
    up = outs2["Out"][0, 0]
    assert up[1, 1] == 7.0                    # written once, not 28.0
    assert up.sum() == 7.0


def test_max_pool3d_with_index():
    x = _rand(1, 2, 4, 4, 4)
    outs, _ = run_single_op(
        "max_pool3d_with_index", {"X": x},
        {"ksize": [2, 2, 2], "strides": [2, 2, 2]}, ["Out", "Mask"])
    ref = x.reshape(1, 2, 2, 2, 2, 2, 2, 2).max(axis=(3, 5, 7))
    np.testing.assert_allclose(outs["Out"], ref, rtol=1e-6)
    flat = x.reshape(1, 2, 64)
    np.testing.assert_allclose(
        np.take_along_axis(flat, outs["Mask"].reshape(1, 2, 8), 2),
        ref.reshape(1, 2, 8), rtol=1e-6)


@pytest.mark.slow
def test_crop_and_space_to_depth():
    x = _rand(2, 3, 6, 6)
    outs, _ = run_single_op(
        "crop", {"X": x}, {"shape": [2, 2, 3, 3],
                           "offsets": [0, 1, 2, 1]}, ["Out"])
    np.testing.assert_allclose(outs["Out"], x[:2, 1:3, 2:5, 1:4])
    check_grad("crop", {"X": x},
               {"shape": [1, 2, 3, 3], "offsets": [0, 0, 1, 1]},
               ["Out"], ["X"], rtol=1e-2, atol=1e-3)
    bs = 2
    outs, _ = run_single_op("space_to_depth", {"X": x},
                            {"blocksize": bs}, ["Out"])
    ref = x.reshape(2, 3, 3, 2, 3, 2).transpose(0, 3, 5, 1, 2, 4) \
        .reshape(2, 12, 3, 3)
    np.testing.assert_allclose(outs["Out"], ref)
    check_grad("space_to_depth", {"X": x}, {"blocksize": 2}, ["Out"],
               ["X"], rtol=1e-2, atol=1e-3)


def test_deformable_conv_zero_offset_matches_conv2d():
    """With zero offsets and unit mask, deformable conv == plain conv."""
    x = _rand(1, 2, 5, 5)
    w = _rand(3, 2, 3, 3, seed=1)
    Ho = Wo = 5
    off = np.zeros((1, 2 * 9, Ho, Wo), np.float32)
    msk = np.ones((1, 9, Ho, Wo), np.float32)
    ref, _ = run_single_op("conv2d", {"Input": x, "Filter": w},
                           {"strides": [1, 1], "paddings": [1, 1]},
                           ["Output"])
    got, _ = run_single_op(
        "deformable_conv", {"Input": x, "Offset": off, "Mask": msk,
                            "Filter": w},
        {"strides": [1, 1], "paddings": [1, 1], "deformable_groups": 1},
        ["Output"])
    np.testing.assert_allclose(got["Output"], ref["Output"], rtol=1e-4,
                               atol=1e-4)
    got1, _ = run_single_op(
        "deformable_conv_v1", {"Input": x, "Offset": off, "Filter": w},
        {"strides": [1, 1], "paddings": [1, 1], "deformable_groups": 1},
        ["Output"])
    np.testing.assert_allclose(got1["Output"], ref["Output"], rtol=1e-4,
                               atol=1e-4)


@pytest.mark.slow
def test_deformable_conv_offset_shifts():
    """An integer offset of (0, 1) everywhere equals convolving the
    x-shifted image (interior pixels)."""
    x = _rand(1, 1, 6, 6)
    w = _rand(1, 1, 1, 1, seed=1)
    off = np.zeros((1, 2, 6, 6), np.float32)
    off[:, 1] = 1.0  # shift x by +1
    got, _ = run_single_op(
        "deformable_conv_v1", {"Input": x, "Offset": off, "Filter": w},
        {"strides": [1, 1], "paddings": [0, 0]}, ["Output"])
    ref = x[:, :, :, 1:] * w[0, 0, 0, 0]
    np.testing.assert_allclose(got["Output"][:, :, :, :-1], ref,
                               rtol=1e-4, atol=1e-5)
    check_grad(
        "deformable_conv_v1",
        {"Input": x, "Offset": off, "Filter": w},
        {"strides": [1, 1], "paddings": [0, 0]}, ["Output"],
        ["Input", "Filter"], rtol=1e-2, atol=1e-2)


def test_nce_structure():
    x = _rand(4, 8)
    w = _rand(20, 8, seed=1)
    b = _rand(20, seed=2)
    lab = np.array([[3], [7], [0], [19]], np.int64)
    outs, _ = run_single_op(
        "nce", {"Input": x, "Label": lab, "Weight": w, "Bias": b},
        {"num_neg_samples": 5, "num_total_classes": 20},
        ["Cost", "SampleLogits", "SampleLabels"])
    assert outs["Cost"].shape == (4, 1) and (outs["Cost"] > 0).all()
    assert outs["SampleLogits"].shape == (4, 6)
    np.testing.assert_array_equal(outs["SampleLabels"][:, 0], lab[:, 0])
    # positive logit matches the manual projection
    ref0 = (x * w[lab[:, 0]]).sum(1) + b[lab[:, 0]]
    np.testing.assert_allclose(outs["SampleLogits"][:, 0], ref0,
                               rtol=1e-4, atol=1e-4)


def test_hierarchical_sigmoid_custom_tree():
    x = _rand(2, 4)
    w = _rand(5, 4, seed=1)
    lab = np.array([[0], [1]], np.int64)
    table = np.array([[0, 2, -1], [0, 3, 4]], np.int64)
    code = np.array([[1, 0, 0], [0, 1, 1]], np.float32)
    outs, _ = run_single_op(
        "hierarchical_sigmoid",
        {"X": x, "Label": lab, "W": w, "PathTable": table,
         "PathCode": code},
        {"num_classes": 5}, ["Out", "PreOut"])
    pre = np.einsum("bd,bld->bl", x, w[np.maximum(table, 0)])
    valid = (table >= 0)
    ce = np.log1p(np.exp(pre)) - code * pre
    ref = (ce * valid).sum(1, keepdims=True)
    np.testing.assert_allclose(outs["Out"], ref, rtol=1e-4, atol=1e-4)


def test_lstmp_projection_shape_and_identity():
    """lstmp with ProjWeight = I (P == D) must reduce to plain lstm."""
    B, T, D = 2, 4, 3
    x = _rand(B, T, 4 * D)
    W = _rand(D, 4 * D, seed=1) * 0.2
    bias = _rand(1, 4 * D, seed=2) * 0.1
    eye = np.eye(D, dtype=np.float32)
    ref, _ = run_single_op(
        "lstm", {"Input": x, "Weight": W, "Bias": bias},
        {}, ["Hidden", "Cell"])
    got, _ = run_single_op(
        "lstmp", {"Input": x, "Weight": W, "ProjWeight": eye,
                  "Bias": bias}, {}, ["Projection", "Cell"])
    np.testing.assert_allclose(got["Projection"], ref["Hidden"],
                               rtol=1e-4, atol=1e-5)
    # real projection changes the emitted width
    Wp = _rand(D, 2, seed=3)
    got2, _ = run_single_op(
        "lstmp", {"Input": x, "Weight": _rand(2, 4 * D, seed=4) * 0.2,
                  "ProjWeight": Wp, "Bias": bias}, {}, ["Projection"])
    assert got2["Projection"].shape == (B, T, 2)


def test_prroi_pool_constant_field():
    """On a constant feature map every bin averages to the constant."""
    x = np.full((1, 2, 8, 8), 3.0, np.float32)
    rois = np.array([[0, 1.0, 1.0, 6.0, 6.0]], np.float32)
    outs, _ = run_single_op(
        "prroi_pool", {"X": x, "ROIs": rois},
        {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0},
        ["Out"])
    np.testing.assert_allclose(outs["Out"], np.full((1, 2, 2, 2), 3.0),
                               rtol=1e-5)


def test_yolov3_loss_finite_and_masks():
    B, A, C, H = 2, 3, 4, 4
    x = _rand(B, A * (5 + C), H, H) * 0.1
    gtbox = np.zeros((B, 2, 4), np.float32)
    gtbox[0, 0] = [0.5, 0.5, 0.3, 0.4]
    gtbox[1, 0] = [0.25, 0.75, 0.2, 0.2]
    gtlabel = np.array([[1, 0], [3, 0]], np.int64)
    outs, _ = run_single_op(
        "yolov3_loss", {"X": x, "GTBox": gtbox, "GTLabel": gtlabel},
        {"anchors": [10, 13, 16, 30, 33, 23], "anchor_mask": [0, 1, 2],
         "class_num": C, "ignore_thresh": 0.7, "downsample_ratio": 32},
        ["Loss", "ObjectnessMask", "GTMatchMask"])
    assert outs["Loss"].shape == (B,)
    assert np.isfinite(outs["Loss"]).all() and (outs["Loss"] > 0).all()
    assert outs["GTMatchMask"].shape == (B, 2)
    assert outs["GTMatchMask"][0, 0] >= 0      # real gt matched
    assert outs["GTMatchMask"][0, 1] == -1     # zero-size gt unmatched


def test_multiclass_nms2_and_ctc_align():
    bboxes = np.array([[[0, 0, 10, 10], [50, 50, 60, 60]]], np.float32)
    scores = np.zeros((1, 2, 2), np.float32)
    scores[0, 1] = [0.9, 0.8]
    outs, _ = run_single_op(
        "multiclass_nms2", {"BBoxes": bboxes, "Scores": scores},
        {"score_threshold": 0.1, "nms_top_k": 2, "keep_top_k": 2,
         "nms_threshold": 0.3, "background_label": 0}, ["Out", "Index"])
    kept = outs["Out"][0][outs["Out"][0, :, 0] >= 0]
    assert len(kept) == 2
    assert (outs["Index"][0, :, 0] >= 0).sum() == 2
    seq = np.array([[0, 1, 1, 0, 2, 2, 3]], np.int32)
    outs, _ = run_single_op("ctc_align", {"Input": seq},
                            {"blank": 0, "padding_value": 0}, ["Output"])
    np.testing.assert_array_equal(outs["Output"][0][:3], [1, 2, 3])
    assert (outs["Output"][0][3:] == 0).all()


def test_positive_negative_pair():
    s = np.array([0.9, 0.2, 0.5, 0.7], np.float32)[:, None]
    lab = np.array([2, 0, 1, 0], np.float32)[:, None]
    q = np.array([1, 1, 1, 2], np.int64)[:, None]
    outs, _ = run_single_op(
        "positive_negative_pair", {"Score": s, "Label": lab, "QueryID": q},
        {}, ["PositivePair", "NegativePair", "NeutralPair"])
    # query 1 ordered label pairs: (0,1):pos, (0,2):pos, (2,1):pos
    assert float(outs["PositivePair"]) == 3
    assert float(outs["NegativePair"]) == 0


def test_mine_hard_examples():
    loss = np.array([[0.9, 0.1, 0.8, 0.2, 0.7]], np.float32)
    match = np.array([[2, -1, -1, -1, -1]], np.int32)
    outs, _ = run_single_op(
        "mine_hard_examples", {"ClsLoss": loss, "MatchIndices": match},
        {"neg_pos_ratio": 2.0}, ["NegIndices", "UpdatedMatchIndices"])
    negs = outs["NegIndices"][0]
    assert set(negs[negs >= 0].tolist()) == {2, 4}  # two hardest unmatched


def test_fused_bn_act_and_inplace_abn():
    x = _rand(4, 3, 2, 2)
    common = {"X": x, "Scale": np.ones(3, np.float32),
              "Bias": np.zeros(3, np.float32),
              "Mean": np.zeros(3, np.float32),
              "Variance": np.ones(3, np.float32)}
    mu = x.mean((0, 2, 3))
    v = x.var((0, 2, 3))
    norm = (x - mu.reshape(1, 3, 1, 1)) / np.sqrt(
        v.reshape(1, 3, 1, 1) + 1e-5)
    outs, _ = run_single_op("fused_batch_norm_act", common,
                            {"epsilon": 1e-5, "act_type": "relu"}, ["Y"])
    np.testing.assert_allclose(outs["Y"], np.maximum(norm, 0), rtol=1e-4,
                               atol=1e-4)
    outs, _ = run_single_op(
        "inplace_abn", common,
        {"epsilon": 1e-5, "activation": "leaky_relu", "alpha": 0.1},
        ["Y"])
    np.testing.assert_allclose(outs["Y"],
                               np.where(norm >= 0, norm, 0.1 * norm),
                               rtol=1e-4, atol=1e-4)


def test_tensor_array_to_tensor_lengths():
    a = [_rand(2, 3), _rand(3, 3, seed=1)]
    outs, _ = run_single_op("tensor_array_to_tensor", {"X": a},
                            {"axis": 0}, ["Out", "OutIndex"])
    np.testing.assert_allclose(outs["Out"], np.concatenate(a, 0),
                               rtol=1e-6)
    np.testing.assert_array_equal(outs["OutIndex"], [2, 3])
    outs, _ = run_single_op("lod_array_length", {"X": a}, {}, ["Out"])
    assert int(outs["Out"][0]) == 2
    outs, _ = run_single_op("max_sequence_len",
                            {"RankTable": _rand(2, 7, 3)}, {}, ["Out"])
    assert int(outs["Out"][0]) == 7


def test_prroi_pool_batch_roi_nums():
    """[R,4] ROIs + BatchRoINums route each ROI to its own image."""
    x = np.zeros((2, 1, 4, 4), np.float32)
    x[0] = 1.0
    x[1] = 5.0
    rois = np.array([[0.5, 0.5, 3.0, 3.0]] * 3, np.float32)
    nums = np.array([1, 2], np.int64)
    outs, _ = run_single_op(
        "prroi_pool", {"X": x, "ROIs": rois, "BatchRoINums": nums},
        {"pooled_height": 1, "pooled_width": 1, "spatial_scale": 1.0},
        ["Out"])
    np.testing.assert_allclose(outs["Out"][:, 0, 0, 0], [1.0, 5.0, 5.0],
                               rtol=1e-5)


def test_nce_noise_correction():
    """The NCE posterior subtracts log(k*q): with logits == log(k*q) the
    positive-term cost is exactly log(2)."""
    total, k = 10, 5
    x = np.ones((1, 2), np.float32)
    # craft weight/bias so the positive logit == log(k/total)
    w = np.zeros((total, 2), np.float32)
    b = np.full((total,), np.log(k / total), np.float32)
    lab = np.array([[0]], np.int64)
    outs, _ = run_single_op(
        "nce", {"Input": x, "Label": lab, "Weight": w, "Bias": b},
        {"num_neg_samples": k, "num_total_classes": total}, ["Cost"])
    # every sampled logit is log(k q) -> adjusted 0 -> each term log 2
    np.testing.assert_allclose(outs["Cost"][0, 0], (1 + k) * np.log(2),
                               rtol=1e-4)


def test_assert_op_passes_and_raises_from_inside_the_compiled_program():
    """`Assert` is a host callback in the compiled step.  It has no
    warn-and-continue branch any more (PR 22): on a backend with host
    callbacks it works, elsewhere the step fails loudly."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 3], append_batch_size=False)
        total = layers.reduce_sum(x)
        layers.control_flow.Assert(
            layers.less_than(total, layers.fill_constant([1], "float32", 10.0)),
            data=[total], message="total too large")
        out = total * 2.0
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    got, = exe.run(main, feed={"x": np.ones((2, 3), np.float32)},
                   fetch_list=[out])
    assert float(np.asarray(got).reshape(-1)[0]) == 12.0
    with pytest.raises(Exception, match="Assert failed: total too large"):
        exe.run(main, feed={"x": np.full((2, 3), 5.0, np.float32)},
                fetch_list=[out])
