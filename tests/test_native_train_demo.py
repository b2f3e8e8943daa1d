"""C++ training demo (reference `train/demo/`,
`train/test_train_recognize_digits.cc`): compile the embedded-runtime
native program and run its training loop to convergence."""

import os
import shutil
import subprocess
import sys
import sysconfig

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "paddle_tpu", "native", "train_demo.cc")


def _embed_flags():
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION")
    return (["-I%s" % inc],
            ["-L%s" % libdir, "-lpython%s" % ver, "-ldl", "-lm"])


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cxx_train_demo_compiles_and_converges(tmp_path):
    incs, libs = _embed_flags()
    exe = str(tmp_path / "train_demo")
    build = subprocess.run(
        ["g++", "-O2", SRC] + incs + libs + ["-o", exe],
        capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr

    env = dict(os.environ)
    # a child that does not need the chip is pinned to the CPU before it
    # imports jax: a chip belongs to one process at a time
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run([exe], capture_output=True, text=True,
                         timeout=600, env=env)
    assert run.returncode == 0, (run.stdout, run.stderr)
    assert "C++ training demo OK" in run.stdout, run.stdout
