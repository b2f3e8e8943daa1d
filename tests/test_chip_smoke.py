"""`chip_smoke.py` rehearsed on the CPU, and the compile-cache helper.

The command-line script always requires the chip; `main(tiny=True,
require_tpu=False)` is the function it exposes for this file, which runs
every phase in-process at a tiny size (Pallas kernels in interpret mode)
so that a wrong path, argument or control flow is found here and not on
the chip.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _phase_lines(out):
    return [ln for ln in out.splitlines() if ln.startswith("[phase_")]


def test_one_chip_phases_pass_at_tiny_size(capsys):
    record = chip_smoke.main(tiny=True, require_tpu=False)
    out = capsys.readouterr().out
    assert record["ok"] is True
    assert record["device"]["platform"] == "cpu"
    assert [ln.split("]")[0][1:] for ln in _phase_lines(out)] == [
        "phase_kernels", "phase_train", "phase_static", "phase_serve"]
    # every engine configuration answered every request over HTTP, and
    # the greedy streams matched the plain forward's
    serve = [ln for ln in out.splitlines() if ln.startswith("[serve] engine=")]
    assert len(serve) == 3
    assert all("answered=5" in ln
               and "greedy_equal_to_plain_forward=3" in ln for ln in serve)
    # the dispatch choice is printed for every step function's trace
    assert re.search(r"\battention -> (pallas|naive) \(", out)


def test_four_chip_phases_pass_on_virtual_devices(capsys):
    record = chip_smoke.main(tiny=True, require_tpu=False, four_chips=True)
    out = capsys.readouterr().out
    assert record["ok"] is True
    assert [ln.split("]")[0][1:] for ln in _phase_lines(out)] == [
        "phase_zero2", "phase_tp4"]
    assert "tokens_equal_to_one_chip=true" in out
    assert "opt_state_devices=4" in out


def test_a_failing_phase_is_not_carried_past(monkeypatch):
    def boom(ctx):
        raise ValueError("phase broke")

    monkeypatch.setattr(chip_smoke, "phase_train", boom)
    with pytest.raises(ValueError, match="phase broke"):
        chip_smoke.main(tiny=True, require_tpu=False)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_command_line_refuses_the_cpu(argv):
    """No switch of the script lets it pass without the chip: on the CPU
    it exits non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")]
                         + argv, capture_output=True, text=True, timeout=300,
                         env=env, cwd=REPO)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "needs a TPU" in run.stderr


def test_command_line_has_no_tiny_or_cpu_switch():
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--help"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert run.returncode == 0
    assert "--four-chips" in run.stdout and "--seed" in run.stdout
    assert "tiny" not in run.stdout and "cpu" not in run.stdout.lower()


# ---------------------------------------------------------------------------
# fluid.core.compile_cache
# ---------------------------------------------------------------------------

_PROBE = """
import json, sys
import jax
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append([k, v]), real(k, v))[1]
from paddle_tpu.fluid.core.compile_cache import (
    compile_cache_dir, enable_compile_cache)
got = enable_compile_cache()
print(json.dumps({"dir": got, "same": got == compile_cache_dir(),
                  "cache_dir_updates": [c for c in calls
                      if c[0] == "jax_compilation_cache_dir"],
                  "jax_dir": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir, cwd):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    run = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr
    return json.loads(run.stdout.strip().splitlines()[-1])


def test_compile_cache_dir_is_placed_from_outside_or_fixed(tmp_path):
    # set from outside: the helper makes the directory and calls no
    # config.update that could name another; jax read the variable itself
    outside = str(tmp_path / "from_env")
    got = _probe(outside, cwd=str(tmp_path))
    assert got["dir"] == outside and got["same"]
    assert got["cache_dir_updates"] == []
    assert got["jax_dir"] == outside
    assert os.path.isdir(outside)

    # not set: <checkout>/.jax_cache, whatever the working directory,
    # identical across two processes (the path is part of the cache key)
    try:
        a = _probe(None, cwd=str(tmp_path))
        b = _probe(None, cwd=REPO)
        want = os.path.join(REPO, ".jax_cache")
        assert a["dir"] == b["dir"] == want
        assert a["cache_dir_updates"] == [["jax_compilation_cache_dir", want]]
    finally:
        # the probe compiled nothing, so the directory it made is empty
        try:
            os.rmdir(os.path.join(REPO, ".jax_cache"))
        except OSError:
            pass


def test_explicit_cache_dir_loses_to_the_environment(tmp_path, monkeypatch):
    from paddle_tpu.fluid.core import compile_cache

    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    assert compile_cache.enable_compile_cache(str(tmp_path / "mine")) \
        == outside
    assert not os.path.exists(str(tmp_path / "mine"))
