"""Multi-process loss/param parity over distributed/launch.py (reference
`tests/unittests/test_dist_base.py:506` check_with_place: spawn trainers,
compare against the single-process run within delta)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_single(tmp_path):
    out = str(tmp_path / "single")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("PADDLE_TRAINERS_NUM", None)
    env.pop("PADDLE_TRAINER_ID", None)
    subprocess.run(
        [sys.executable, WORKER, out], env=env, check=True, timeout=300,
        capture_output=True,
    )
    with open(os.path.join(out, "result_0.json")) as f:
        return json.load(f)


def _run_multi(tmp_path, nproc=2):
    out = str(tmp_path / "multi")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [
            sys.executable, "-m", "paddle_tpu.distributed.launch",
            "--nproc_per_node=%d" % nproc,
            "--started_port=%d" % _free_port(),
            WORKER, out,
        ],
        env=env, timeout=600, capture_output=True, text=True,
    )
    assert p.returncode == 0, "launch failed:\n%s\n%s" % (p.stdout, p.stderr)
    results = []
    for r in range(nproc):
        with open(os.path.join(out, "result_%d.json" % r)) as f:
            results.append(json.load(f))
    return results


def test_two_process_loss_parity(tmp_path):
    single = _run_single(tmp_path)
    multi = _run_multi(tmp_path, nproc=2)

    # params: every rank must end bit-close to the single-process params
    # (c_allreduce_sum made the updates globally identical)
    for r, res in enumerate(multi):
        np.testing.assert_allclose(
            res["w"], single["w"], rtol=1e-5, atol=1e-6,
            err_msg="rank %d params diverged from single-process" % r,
        )

    # losses: mean of the ranks' local losses == global-batch loss
    merged = np.mean([res["losses"] for res in multi], axis=0)
    np.testing.assert_allclose(merged, single["losses"], rtol=1e-5, atol=1e-6)
    # and training progressed
    assert single["losses"][-1] < single["losses"][0]


def test_mesh_mode_transpiled_parity_single_process():
    """Executor mesh mode on 8 virtual devices: the GradAllReduce-transpiled
    program (real psum inside shard_map) matches the plain single-device
    run on the same global batch."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu import distributed as dist
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.transpiler.collective import GradAllReduce

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[-1, 8], append_batch_size=False)
            y = layers.data("y", shape=[-1, 1], append_batch_size=False)
            h = layers.fc(x, size=16, act="relu")
            pred = layers.fc(h, size=1)
            loss = layers.reduce_mean(layers.square(pred - y))
            fluid.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    xs = rng.randn(4, 16, 8).astype(np.float32)
    ys = rng.randn(4, 16, 1).astype(np.float32)

    # plain single-device
    main, startup, loss = build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    plain = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for t in range(4):
            (lv,) = exe.run(main, feed={"x": xs[t], "y": ys[t]},
                            fetch_list=[loss])
            plain.append(float(lv))
        w_plain = np.asarray(scope.find_var(main.all_parameters()[0].name))

    # transpiled + mesh mode over 8 virtual ranks
    main, startup, loss = build()
    eps = ["127.0.0.1:%d" % (6170 + i) for i in range(8)]
    GradAllReduce().transpile(startup_program=startup, main_program=main,
                              rank=0, endpoints=eps)
    assert any(op.type == "c_allreduce_sum"
               for op in main.global_block.ops)
    mesh = dist.DeviceMesh({"dp": 8}, devices=jax.devices())
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), mesh=mesh)
    sharded = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for t in range(4):
            (lv,) = exe.run(main, feed={"x": xs[t], "y": ys[t]},
                            fetch_list=[loss])
            assert lv.shape[0] == 8  # one local loss per rank
            sharded.append(float(np.mean(lv)))
        w_mesh = np.asarray(scope.find_var(main.all_parameters()[0].name))

    np.testing.assert_allclose(sharded, plain, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_mesh, w_plain, rtol=1e-5, atol=1e-6)
