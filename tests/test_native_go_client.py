"""Go cgo client over the C ABI (reference `go/paddle/predictor.go`
capability — the last open parity row from VERDICT r5): build
libpaddle_tpu_capi.so, save a model, and run the `go/paddle_tpu`
package's test, which must reproduce the Python Predictor's outputs.

Gated on the toolchain: no g++ (cannot build the .so) or no Go
toolchain -> clean skip with the reason, per the satellite contract."""

import os
import shutil
import struct
import subprocess
import sysconfig

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "paddle_tpu", "native")
GO_PKG = os.path.join(REPO, "go", "paddle_tpu")


def _embed_flags():
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION")
    return (["-I%s" % inc, "-I%s" % NATIVE],
            ["-L%s" % libdir, "-lpython%s" % ver, "-ldl", "-lm"])


def _save_fc_model(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 8], append_batch_size=False)
        pred = layers.fc(layers.fc(x, 16, act="relu"), 4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    path = str(tmp_path / "fc.model")
    fluid.io.save_inference_model(path, ["x"], [pred], exe, main)
    return path


def _write_bin(path, arr):
    arr = np.ascontiguousarray(arr, np.float32)
    with open(path, "wb") as f:
        f.write(struct.pack("<q", arr.ndim))
        for d in arr.shape:
            f.write(struct.pack("<q", d))
        f.write(arr.tobytes())


@pytest.mark.skipif(shutil.which("g++") is None,
                    reason="no g++ to build libpaddle_tpu_capi.so")
@pytest.mark.skipif(shutil.which("go") is None,
                    reason="no Go toolchain; the cgo client cannot be "
                           "smoke-tested in this environment")
def test_go_client_matches_python_predictor(tmp_path):
    incs, libs = _embed_flags()
    so = str(tmp_path / "libpaddle_tpu_capi.so")
    build = subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC",
         os.path.join(NATIVE, "infer_capi.cc")] + incs + libs + ["-o", so],
        capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr

    model_dir = _save_fc_model(tmp_path)
    rng = np.random.RandomState(4)
    x = rng.randn(3, 8).astype(np.float32)

    from paddle_tpu.inference import AnalysisConfig, create_predictor

    want, = create_predictor(AnalysisConfig(model_dir)).run([x])

    input_bin = str(tmp_path / "input.bin")
    expected_bin = str(tmp_path / "expected.bin")
    _write_bin(input_bin, x)
    _write_bin(expected_bin, want)

    env = dict(os.environ)
    env.update({
        "PADDLE_TPU_TEST_MODEL_DIR": model_dir,
        "PADDLE_TPU_TEST_INPUT": input_bin,
        "PADDLE_TPU_TEST_EXPECTED": expected_bin,
        "CGO_ENABLED": "1",
        "CGO_CFLAGS": "-I%s" % NATIVE,
        "CGO_LDFLAGS": "%s -Wl,-rpath,%s" % (so, str(tmp_path)),
        "GOCACHE": str(tmp_path / "gocache"),
        "GOFLAGS": "-count=1",
        # the embedded interpreter must match this test's backend setup
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "JAX_DEFAULT_MATMUL_PRECISION": "highest",
    })
    run = subprocess.run(
        ["go", "test", "-v", "-run", "TestPredictorMatchesPython", "./..."],
        cwd=GO_PKG, capture_output=True, text=True, timeout=600, env=env)
    assert run.returncode == 0, (run.stdout, run.stderr)
    assert "PASS" in run.stdout, run.stdout
    assert "SKIP" not in run.stdout, run.stdout


def test_go_package_sources_are_wellformed():
    """Toolchain-independent floor: the Go package ships, declares the
    documented API surface, and binds every C ABI symbol — so a
    go-less CI still guards against bitrot of the source itself."""
    src = open(os.path.join(GO_PKG, "paddle_tpu.go")).read()
    for sym in ("PD_CreatePredictor", "PD_Run", "PD_DeletePredictor",
                "PD_GetInputNum", "PD_GetInputName", "PD_GetOutputNum",
                "PD_GetOutputName"):
        assert sym in src, "C ABI symbol %s unbound in the Go client" % sym
    for api in ("func NewPredictor", "func (p *Predictor) Run",
                "func (p *Predictor) InputNames",
                "func (p *Predictor) OutputNames",
                "func (p *Predictor) Close", "type Tensor struct"):
        assert api in src, "Go client API %r missing" % api
    assert os.path.exists(os.path.join(GO_PKG, "go.mod"))
    header = open(os.path.join(NATIVE, "paddle_tpu_capi.h")).read()
    # every symbol the client binds must exist in the header it compiles
    # against
    for sym in ("PD_CreatePredictor", "PD_Run", "PD_DeletePredictor"):
        assert sym in header
