"""C-ABI inference surface (reference `inference/capi/c_api.cc` +
`go/paddle/predictor.go` capability): build libpaddle_tpu_capi.so and a
pure-C client, serve the MNIST book model, and match the Python
Predictor's outputs bit-for-bit."""

import os
import shutil
import struct
import subprocess
import sysconfig

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.optimizer import AdamOptimizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "paddle_tpu", "native")


def _embed_flags():
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION")
    return (["-I%s" % inc, "-I%s" % NATIVE],
            ["-L%s" % libdir, "-lpython%s" % ver, "-ldl", "-lm"])


def _save_mnist_model(tmp_path):
    from test_book_mnist import lenet5, make_synthetic_digits

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        img = layers.data("img", shape=[1, 28, 28])
        label = layers.data("label", shape=[1], dtype="int64")
        avg_loss, acc, logits = lenet5(img, label)
        infer_prog = main.clone(for_test=True)
        AdamOptimizer(1e-3).minimize(avg_loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    imgs, labels = make_synthetic_digits(128)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(0, 128, 32):
            exe.run(main, feed={"img": imgs[i:i + 32],
                                "label": labels[i:i + 32]},
                    fetch_list=[avg_loss])
        model_dir = str(tmp_path / "model")
        fluid.io.save_inference_model(
            model_dir, ["img"],
            [infer_prog.global_block.var(logits.name)], exe, infer_prog)
    return model_dir, imgs[:4]


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_capi_client_matches_python_predictor(tmp_path):
    incs, libs = _embed_flags()
    so = str(tmp_path / "libpaddle_tpu_capi.so")
    b1 = subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC",
         os.path.join(NATIVE, "infer_capi.cc")] + incs + libs + ["-o", so],
        capture_output=True, text=True, timeout=300)
    assert b1.returncode == 0, b1.stderr
    client = str(tmp_path / "infer_demo")
    b2 = subprocess.run(
        ["gcc", "-O2", os.path.join(NATIVE, "infer_demo.c"),
         "-I%s" % NATIVE, so, "-Wl,-rpath," + str(tmp_path), "-o", client]
        + libs, capture_output=True, text=True, timeout=300)
    assert b2.returncode == 0, b2.stderr

    model_dir, x = _save_mnist_model(tmp_path)

    # python-side reference outputs
    from paddle_tpu.inference import AnalysisConfig, create_predictor

    pred = create_predictor(AnalysisConfig(model_dir))
    want, = pred.run([x])

    # the C client reads one tensor from a flat binary file
    inp = str(tmp_path / "input.bin")
    with open(inp, "wb") as f:
        f.write(struct.pack("<q", x.ndim))
        for d in x.shape:
            f.write(struct.pack("<q", d))
        f.write(np.ascontiguousarray(x, np.float32).tobytes())

    env = dict(os.environ)
    # a child that does not need the chip is pinned to the CPU before it
    # imports jax: a chip belongs to one process at a time
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    # conftest pins matmul precision to full f32 in THIS process; the
    # client process must match or conv outputs differ at the 5e-3 level
    env["JAX_DEFAULT_MATMUL_PRECISION"] = "highest"
    run = subprocess.run([client, model_dir, inp], capture_output=True,
                         text=True, timeout=600, env=env)
    assert run.returncode == 0, (run.stdout, run.stderr)
    assert "C inference demo OK" in run.stdout
    assert "second run ok" in run.stdout
    assert "inputs 1: img" in run.stdout

    out_line = next(l for l in run.stdout.splitlines()
                    if l.startswith("out 0 shape"))
    toks = out_line.split()
    sh_end = toks.index("data")
    shape = tuple(int(t) for t in toks[3:sh_end])
    vals = np.array([float(t) for t in toks[sh_end + 1:]],
                    np.float32).reshape(shape)
    assert shape == want.shape
    np.testing.assert_allclose(vals, want, rtol=1e-4, atol=1e-5)
