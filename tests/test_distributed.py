"""Distributed subsystem on the 8-device host-simulated mesh.

Mirrors the reference distributed test strategy (SURVEY §4.3): collective
ops compared against numpy on simulated ranks, and *loss parity* — the
sharded multi-device step must match the single-device run within delta
(cf. test_dist_base.check_with_place).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import distributed as dist
from paddle_tpu import models
from paddle_tpu.fluid import dygraph
from paddle_tpu.fluid.optimizer import AdamOptimizer, SGDOptimizer


def test_mesh_construction():
    mesh = dist.auto_mesh(8, tp=2)
    assert mesh.axis_size("tp") == 2
    assert mesh.axis_size("dp") == 4
    assert mesh.size == 8
    # tp innermost (ICI), dp outermost (cf. scaling-book recipe)
    assert mesh.axis_names[-1] == "tp"
    assert mesh.axis_names[0] == "dp"


def test_collectives_under_shard_map():
    from jax.sharding import PartitionSpec as P

    mesh = dist.auto_mesh(8)

    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def body(x):
        s = dist.all_reduce(x, "sum", axis="dp")
        mx = dist.all_reduce(x, "max", axis="dp")
        g = dist.all_gather(x, axis="dp")
        return s, mx, g

    s, mx, g = jax.shard_map(
        body, mesh=mesh.mesh,
        in_specs=(P("dp", None),),
        out_specs=(P("dp", None), P("dp", None), P("dp", None)),
    )(x)
    np.testing.assert_allclose(np.asarray(s)[:, 0], [28.0] * 8)
    np.testing.assert_allclose(np.asarray(mx)[:, 0], [7.0] * 8)
    assert np.asarray(g).shape == (64, 1)  # 8 ranks x tiled gather


def test_collective_program_ops_single_rank_identity():
    """c_* ops outside any mesh = world size 1 = identity (reference
    single-trainer behavior)."""
    from paddle_tpu.fluid.core.registry import LowerContext, get_op_def

    ctx = LowerContext()
    x = jnp.ones((3,))
    for op in ["c_allreduce_sum", "c_broadcast", "c_sync_comm_stream"]:
        out = get_op_def(op).lower(ctx, {"X": [x]}, {"ring_id": 0})
        np.testing.assert_allclose(np.asarray(out["Out"][0]), np.ones(3))


def test_send_recv_ring_shift():
    from jax.sharding import PartitionSpec as P

    mesh = dist.auto_mesh(8)
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def body(x):
        return dist.send_recv(x, perm, axis="dp")

    out = jax.shard_map(body, mesh=mesh.mesh, in_specs=(P("dp", None),),
                        out_specs=P("dp", None))(x)
    np.testing.assert_allclose(
        np.asarray(out)[:, 0], [7, 0, 1, 2, 3, 4, 5, 6]
    )


def _bert_batch(cfg, B, S, seed):
    rng = np.random.RandomState(seed)
    return {
        "input_ids": rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int64),
        "token_type_ids": np.zeros((B, S), np.int64),
        "position_ids": np.tile(np.arange(S, dtype=np.int64), (B, 1)),
        "mlm_labels": rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int64),
        "mlm_weights": np.ones((B, S), np.float32),
        "nsp_labels": rng.randint(0, 2, (B, 1)).astype(np.int64),
    }


def _bert_loss_fn(model, batch):
    logits, nsp_logits = model(
        batch["input_ids"], batch["token_type_ids"], batch["position_ids"]
    )
    return model.loss(
        logits, nsp_logits, batch["mlm_labels"], batch["mlm_weights"],
        batch["nsp_labels"],
    )


def _run_steps(mesh_kw, n_steps=3, seed=0):
    cfg = models.BertConfig.tiny()
    with dygraph.guard():
        tr_framework = __import__(
            "paddle_tpu.fluid.framework", fromlist=["x"]
        )._dygraph_tracer
        tr_framework._base_key = jax.random.PRNGKey(7)  # deterministic init
        np.random.seed(seed)
        import paddle_tpu.fluid.unique_name as un

        model = models.BertForPretraining(cfg)
        opt = AdamOptimizer(learning_rate=1e-3)
        mesh = dist.auto_mesh(**mesh_kw)
        step = dist.ShardedTrainStep(model, opt, _bert_loss_fn, mesh)
        state = step.init()
        losses = []
        for i in range(n_steps):
            batch = _bert_batch(cfg, 8, 16, seed=100 + i)
            state, loss = step(state, batch)
            losses.append(float(loss))
        return losses


@pytest.fixture(autouse=True)
def _fresh_names():
    from paddle_tpu.fluid import unique_name

    old = unique_name.switch()
    yield
    unique_name.switch(old)


def test_dp_loss_parity_with_single_device():
    """8-way data parallel must match 1-device losses (test_dist_base
    pattern).  Model init must be identical: both runs seed the tracer the
    same way, and jax PRNG is deterministic."""
    single = _run_steps({"n_devices": 1})
    dp8 = _run_steps({"n_devices": 8})
    np.testing.assert_allclose(single, dp8, rtol=2e-3, atol=2e-4)


def test_tp_sp_loss_parity_with_single_device():
    """dp2 x tp2 x sp2 sharded step matches single device."""
    single = _run_steps({"n_devices": 1})
    mixed = _run_steps({"n_devices": 8, "tp": 2, "sp": 2})
    np.testing.assert_allclose(single, mixed, rtol=2e-3, atol=2e-4)


def test_zero_sharded_optimizer_state():
    """ZeRO-1: adam moments are dp-sharded across devices."""
    cfg = models.BertConfig.tiny()
    with dygraph.guard():
        model = models.BertForPretraining(cfg)
        opt = AdamOptimizer(learning_rate=1e-3)
        mesh = dist.auto_mesh(8)
        step = dist.ShardedTrainStep(model, opt, _bert_loss_fn, mesh, zero_stage=1)
        state = step.init()
        # find a large param's moment and check its sharding spans dp
        name = "bert.embeddings.word.weight"
        m1 = state["opt"][name]["Moment1"]
        assert "dp" in str(m1.sharding.spec)


def test_parallel_env_contract(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "8")
    monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS", ",".join(
        "127.0.0.1:617%d" % i for i in range(8)
    ))
    env = dist.ParallelEnv()
    assert env.rank == 3
    assert env.world_size == 8
    assert len(env.trainer_endpoints) == 8


def test_sharded_train_step_handles_changed_batch_shape():
    """A batch with a different shape (e.g. the last partial batch) gets
    its own compiled step with correct shardings instead of a stale
    retrace against the first batch's in_shardings."""
    cfg = models.BertConfig.tiny()
    with dygraph.guard():
        from paddle_tpu.fluid import framework as _fw

        _fw._dygraph_tracer._base_key = jax.random.PRNGKey(7)
        model = models.BertForPretraining(cfg)
        opt = AdamOptimizer(learning_rate=1e-3)
        mesh = dist.auto_mesh(8)
        step = dist.ShardedTrainStep(model, opt, _bert_loss_fn, mesh)
        state = step.init()
        state, l1 = step(state, _bert_batch(cfg, 8, 16, seed=1))
        state, l2 = step(state, _bert_batch(cfg, 4, 16, seed=2))  # smaller B
        state, l3 = step(state, _bert_batch(cfg, 8, 16, seed=3))  # back
        assert len(step._step_fns) == 2
        assert all(np.isfinite(x) for x in (float(l1), float(l2), float(l3)))
