"""`paddle_tpu.tp_serving`: tensor-parallel decode, expert-parallel
MoE, and disaggregated prefill/decode serving.

The load-bearing drills:

* **token identity** — the TP engine is the SAME product as the
  single-chip engine, token for token at fixed seeds, under mixed
  greedy/sampled traffic with mid-flight slot refill.  Sharding the
  matmuls must change the numerics not at all (psum of exact column
  partials) — any drift is a layout bug, not a tolerance matter;
* **compile discipline** — one decode executable, one prefill
  executable per bucket, for the LIFE of the engine (the PR-15 pin
  carried into shard_map land, including the sharding-commitment
  trap: a fresh engine's arrays must already carry the steady-state
  `NamedSharding` or call #2 of each bucket silently doubles the
  executable set);
* **comm pinning** — `decode_comm_estimate` vs the compiled HLO's
  per-layer all-reduces EXACTLY (count and wire bytes), and the EP
  MoE's two all-to-alls priced to the byte by `ep_moe_comm_bytes` —
  the PR-13 estimate-vs-compiled discipline;
* **role separation** — a disaggregated decode worker never traces a
  prefill bucket; a prefill worker never traces the decode step.

Mesh: the 8 host-platform CPU devices `tests/conftest.py` forces.
"""

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import models
from paddle_tpu.analysis import comm as comm_mod
from paddle_tpu.fluid import dygraph

gen = paddle_tpu.generation
tps = paddle_tpu.tp_serving

CFG = models.TransformerLMConfig.tiny()


@pytest.fixture(scope="module")
def lm():
    with dygraph.guard():
        np.random.seed(0)
        model = models.TransformerLM(CFG)
    return model


def make_engine(model, *, tp=None, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_queue", 64)
    kw.setdefault("block_size", 16)
    kw.setdefault("kv_blocks", 14)
    if tp is None:
        return gen.GenerationEngine(model, **kw)
    return tps.TPGenerationEngine(model, tp=tp, **kw)


def mixed_requests(n, max_new=6):
    """Mixed greedy/sampled traffic, prompts spanning both buckets."""
    rng = np.random.RandomState(1)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(2, 14))
        prompt = rng.randint(0, CFG.vocab_size, plen)
        sp = (gen.SamplingParams.greedy() if i % 2 == 0 else
              gen.SamplingParams(temperature=0.9, top_k=20, top_p=0.9,
                                 seed=100 + i))
        reqs.append(gen.GenerationRequest(
            prompt, max_new_tokens=max_new + (i % 3), sampling=sp))
    return reqs


def run_all(engine, requests):
    handles = [engine.submit(r) for r in requests]
    engine.run_until_idle()
    return [h.result(timeout=30.0) for h in handles]


@pytest.fixture(scope="module")
def baseline(lm):
    """Single-chip token streams for the identity drills."""
    eng = make_engine(lm)
    return run_all(eng, mixed_requests(7))


@pytest.fixture(scope="module")
def tp2(lm):
    return make_engine(lm, tp=2)


# ---------------------------------------------------------------- layout
class TestLayout:
    def test_validate_tp_rejects_bad_degrees(self):
        assert tps.validate_tp(CFG, 2) == 2
        with pytest.raises(ValueError):
            tps.validate_tp(CFG, 0)
        with pytest.raises(ValueError):
            tps.validate_tp(CFG, 3)        # 4 heads % 3 != 0
        with pytest.raises(ValueError):
            tps.validate_tp(CFG, 8)        # > num_heads

    def test_param_specs_column_row_replicated(self, lm):
        specs = tps.tp_param_specs(lm.state_dict().keys())
        qkv = [k for k in specs if k.endswith("qkv_proj.weight")]
        out = [k for k in specs if k.endswith("out_proj.weight")]
        assert qkv and out
        for k in qkv:
            assert tuple(specs[k]) == (None, "tp"), k   # column
        for k in out:
            assert tuple(specs[k]) == ("tp", None), k   # row
        emb = [k for k in specs
               if k.startswith(("word.", "position.")) or ".ln" in k]
        assert emb
        for k in emb:
            assert tuple(specs[k]) == (), k             # replicated

    def test_prepare_restore_roundtrip_bit_exact(self, lm):
        canon = {k: v.numpy() for k, v in lm.state_dict().items()}
        for tp in (2, 4):
            staged = tps.prepare_tp_params(canon, CFG, tp)
            back = tps.restore_tp_params(staged, CFG, tp)
            assert set(back) == set(canon)
            for k in canon:
                np.testing.assert_array_equal(
                    np.asarray(back[k]), canon[k], err_msg=k)
        # the qkv regroup is a real permutation, not the identity
        staged = tps.prepare_tp_params(canon, CFG, 2)
        name = next(k for k in canon if k.endswith("qkv_proj.weight"))
        assert not np.array_equal(staged[name], canon[name])


# ---------------------------------------------------------------- TP engine
class TestTensorParallel:
    def test_tp2_token_identity_mixed_traffic(self, tp2, baseline):
        got = run_all(tp2, mixed_requests(7))
        assert len(got) == len(baseline)
        for i, (a, b) in enumerate(zip(baseline, got)):
            assert a == b, "request %d diverged: %r vs %r" % (i, a, b)

    def test_compile_once_for_the_life_of_the_engine(self, tp2):
        # fixture traffic already hit both buckets, greedy AND sampled
        ex = tp2.stats()["executables"]
        assert ex["decode_step"] == 1
        assert ex["prefill"] == {8: 1, 16: 1}
        run_all(tp2, mixed_requests(5))       # more mixed traffic
        assert tp2.stats()["executables"] == ex

    def test_decode_comm_estimate_matches_hlo_exactly(self, tp2):
        chk = tp2.decode_hlo_comm_check()
        assert chk["count_match"] and chk["wire_match"], chk
        # closed form at tp=2: ring factor 2(N-1)/N == 1, so the wire
        # bytes per step are exactly 2·L·slots·H·4
        L, s, h = CFG.num_layers, tp2.slots, CFG.hidden_size
        assert chk["all_reduce_count"] == 2 * L
        assert chk["comm_bytes_per_step"] == 2 * L * s * h * 4
        # .lower() for the check must not have grown the jit cache
        assert tp2.stats()["executables"]["decode_step"] == 1

    def test_cache_arrays_shard_their_merged_last_dimension(self, lm, tp2):
        """One array per layer for K and for V, each committed to the
        mesh split over its merged ``H*Dh`` dimension (a contiguous
        split of it is a split by heads), before and after traffic;
        an int8 pool's scales split over their ``H`` the same way."""
        from jax.sharding import PartitionSpec as P

        L, hd = CFG.num_layers, CFG.num_heads * CFG.head_dim

        def check(eng, kinds):
            arrays = eng.cache.arrays()
            assert len(arrays) == eng._nc == kinds * L
            assert eng._cache_specs() == (P(None, None, "tp"),) * eng._nc
            for a in arrays[:2 * L]:
                assert a.shape == (eng.cache.num_blocks, 16, hd)
            for a in arrays[2 * L:]:
                assert a.shape == (eng.cache.num_blocks, 16, CFG.num_heads)
            for a in arrays:
                assert tuple(a.sharding.spec) == (None, None, "tp")
                shard = a.addressable_shards[0].data
                assert shard.shape == a.shape[:2] + (a.shape[2] // eng.tp,)
            assert eng.cache.describe()["bytes"] == sum(
                a.nbytes for a in arrays)

        check(tp2, 2)
        run_all(tp2, mixed_requests(2))
        check(tp2, 2)
        check(make_engine(lm, tp=2, kv_dtype="int8"), 4)

    def test_stats_surface_tp_block(self, tp2):
        t = tp2.stats()["tp"]
        assert t["degree"] == 2
        assert t["kv_heads_per_shard"] == CFG.num_heads // 2
        assert t["all_reduces_per_layer"] == 2
        assert len(t["devices"]) == 2

    def test_snapshot_swap_roundtrip_serves_identically(self, lm, tp2):
        canon = {k: v.numpy() for k, v in lm.state_dict().items()}
        snap = tp2.snapshot_params()
        assert set(snap) == set(canon)
        for k in canon:
            np.testing.assert_array_equal(snap[k], canon[k], err_msg=k)
        before = run_all(tp2, mixed_requests(3))
        ex = tp2.stats()["executables"]
        tp2.swap_params(snap)                 # hot-swap same weights
        after = run_all(tp2, mixed_requests(3))
        assert before == after
        assert tp2.stats()["executables"] == ex   # no recompile

    def test_mesh_validation(self, lm):
        with pytest.raises(ValueError):
            tps.tp_mesh(1000)
        import jax
        from jax.sharding import Mesh
        bad = Mesh(np.asarray(jax.devices()[:2]), ("model",))
        with pytest.raises(ValueError):
            tps.TPGenerationEngine(lm, tp=2, mesh=bad)


# ---------------------------------------------------------------- EP MoE
class TestExpertParallel:
    def _build(self, e=8, d=16, h=32, top_k=2):
        with dygraph.guard():
            np.random.seed(3)
            moe = models.MoEFFN(d, h, num_experts=e,
                                capacity_factor=8.0, top_k=top_k)
            params = tps.moe.moe_params(moe)
            x = np.random.RandomState(5).randn(32, d).astype(np.float32)
            ref = moe(dygraph.to_variable(x)).numpy()
        return params, x, ref

    def test_ep_moe_matches_single_chip_with_ample_capacity(self):
        params, x, ref = self._build()
        mesh = tps.tp_mesh(4)
        fn = tps.build_ep_moe(mesh, 8, capacity_factor=8.0, top_k=2)
        out = np.asarray(fn(params, x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_ep_moe_comm_estimate_matches_hlo_exactly(self):
        params, x, _ = self._build()
        n = 4
        mesh = tps.tp_mesh(n)
        fn = tps.build_ep_moe(mesh, 8, capacity_factor=8.0, top_k=2)
        hlo = fn.lower(params, x).compile().as_text()
        stats = comm_mod.hlo_collective_stats(hlo, n)
        est = tps.ep_moe_comm_bytes(32, 16, 8, n, capacity_factor=8.0,
                                    top_k=2)
        a2a = stats.get("all-to-all")
        assert a2a, "compiled EP MoE has no all-to-all: %r" % stats
        assert a2a["count"] == 2                 # dispatch + combine
        assert a2a["wire_bytes"] == pytest.approx(est["wire_bytes"])

    def test_ep_moe_rejects_undividable_experts(self):
        mesh = tps.tp_mesh(4)
        with pytest.raises(ValueError):
            tps.build_ep_moe(mesh, 6)


# ------------------------------------------------------- comm conventions
class TestAllToAllPricing:
    def test_wire_bytes_convention(self):
        # payload = the PER-CHIP buffer; (N-1)/N of it crosses the wire
        assert comm_mod.collective_wire_bytes(
            "all-to-all", 1024, 4) == pytest.approx(768.0)
        assert comm_mod.collective_wire_bytes(
            "all-to-all", 1024, 8) == pytest.approx(896.0)

    def test_hlo_parser_recognises_a2a_forms(self):
        hlo = "\n".join([
            "  %a2a = f32[8,16]{1,0} all-to-all(f32[8,16]{1,0} %p0), "
            "replica_groups={{0,1,2,3}}, dimensions={0}",
            "  %t = (f32[4,8]{1,0}, f32[4,8]{1,0}) all-to-all("
            "f32[4,8]{1,0} %x, f32[4,8]{1,0} %y), "
            "replica_groups={{0,1}}",
        ])
        rows = comm_mod.hlo_collectives(hlo)
        kinds = [r["kind"] for r in rows]
        assert kinds.count("all-to-all") == 2
        assert rows[0]["result_bytes"] == 8 * 16 * 4
        assert rows[1]["result_bytes"] == 2 * 4 * 8 * 4  # tuple form
        stats = comm_mod.hlo_collective_stats(hlo, 4)
        assert stats["all-to-all"]["count"] == 2


# ------------------------------------------------- disaggregated serving
class TestDisaggregation:
    @pytest.fixture(scope="class")
    def pair(self, lm):
        prefill = make_engine(lm, slots=2, kv_blocks=10)
        decode = make_engine(lm, slots=3, kv_blocks=14)
        return tps.DisaggPair(prefill, decode, group_id=0)

    def test_token_identity_and_role_pin(self, lm, pair, baseline):
        handles = [pair.submit(r) for r in mixed_requests(7)]
        pair.run_until_idle()
        got = [h.result(timeout=30.0) for h in handles]
        for i, (a, b) in enumerate(zip(baseline, got)):
            assert a == b, "request %d diverged" % i
        # role separation: the decode worker NEVER traces a prefill
        # bucket; the prefill worker never traces the decode step
        dex = pair.decode.stats()["executables"]
        assert all(v == 0 for v in dex["prefill"].values()), dex
        assert dex["decode_step"] == 1
        pex = pair.prefill.stats()["executables"]
        assert pex["decode_step"] == 0
        assert sum(pex["prefill"].values()) >= 1
        st = pair.stats()
        assert st["handoffs"] == 7
        assert st["kv_transfer_bytes"] > 0
        assert st["roles"]["prefill"] != st["roles"]["decode"]

    def test_handoff_describe_and_nbytes(self, lm, pair):
        req = gen.GenerationRequest([1, 2, 3, 4], max_new_tokens=2)
        handoff = pair.prefill.prefill_extract(req)
        d = handoff.describe()
        assert d["n_prompt"] == 4
        assert d["bytes"] == handoff.nbytes > 0
        # route it on manually so the slot drains
        h = pair.decode.inject_prefilled(handoff)
        pair.run_until_idle()
        assert len(h.result(timeout=30.0)) == 2

    def test_handoff_round_trip_is_exact(self, lm, pair):
        """The wire form is the pool's own: one ``[n_blocks, bs, H*Dh]``
        page array per pool array.  What one engine extracts, a second
        adopts into OTHER blocks and hands on again to the byte, and the
        request decodes from it to the single-engine stream."""
        prompt = list(range(3, 23))                 # 20 tokens: 2 blocks
        want = run_all(make_engine(lm, prefill_buckets=[8, 16, 32]), [
            gen.GenerationRequest(prompt, max_new_tokens=5)])[0]
        first = make_engine(lm, prefill_buckets=[8, 16, 32])
        second = make_engine(lm, prefill_buckets=[8, 16, 32], slots=2,
                             kv_blocks=9)
        handoff = first.prefill_extract(
            gen.GenerationRequest(prompt, max_new_tokens=5))
        L, hd = CFG.num_layers, CFG.num_heads * CFG.head_dim
        assert len(handoff.pages) == 2 * L == len(first.cache.arrays())
        assert all(p.shape == (2, 16, hd) and p.dtype == np.float32
                   for p in handoff.pages)
        assert handoff.describe()["blocks"] == 2
        assert handoff.nbytes == 2 * L * 2 * 16 * hd * 4
        # the prompt's rows are there (the last block's tail holds the
        # padded positions' rows, which nothing reads)
        assert all(np.abs(p.reshape(32, hd)[:20]).sum(-1).all()
                   for p in handoff.pages)
        # occupy the second pool's lowest blocks so the pages land elsewhere
        taken = second.cache.pool.alloc(3)
        h = second.inject_prefilled(handoff)
        second.step()                               # adopts the pages
        ids = np.asarray(second._slot_blocks[
            [i for i, st in enumerate(second._slot_state) if st][0]])
        assert not set(ids.tolist()) & set(taken)
        for page, a in zip(handoff.pages, second.cache.arrays()):
            np.testing.assert_array_equal(
                np.asarray(a)[ids].reshape(32, hd)[:20],
                page.reshape(32, hd)[:20])
        second.run_until_idle()
        assert h.result(timeout=30.0) == want
        second.cache.pool.decref(taken)

    def test_geometry_validation(self, lm, pair):
        req = gen.GenerationRequest([1, 2, 3], max_new_tokens=2)
        handoff = pair.prefill.prefill_extract(req)
        # one page array per pool array, each a pool array's geometry
        assert len(handoff.pages) == len(pair.decode.cache.arrays())
        assert all(p.shape[1:] == a.shape[1:] for p, a in zip(
            handoff.pages, pair.decode.cache.arrays()))
        short = tps.KVHandoff(
            handoff.request, handoff.n_prompt, handoff.tok0, handoff.lp0,
            handoff.key, handoff.pages[:2], handoff.block_size,
            handoff.kv_dtype)
        with pytest.raises(ValueError, match="geometry"):
            pair.decode.inject_prefilled(short)
        dense = gen.GenerationEngine(lm, slots=2, max_len=64,
                                     prefill_buckets=[8], max_queue=8,
                                     paged=False)
        with pytest.raises(ValueError):
            dense.inject_prefilled(handoff)
        other = make_engine(lm, slots=2, block_size=8, kv_blocks=18)
        with pytest.raises(ValueError):
            other.inject_prefilled(handoff)
        with pytest.raises(ValueError):
            tps.DisaggPair(dense, pair.decode)


class _StubGroup:
    """Headroom-controllable stand-in: ShardGroupFleet routes on the
    (headroom, -group_id) key and calls nothing else on submit."""

    def __init__(self, group_id, headroom):
        self.group_id = group_id
        self._headroom = headroom
        self.kv_transfer_bytes = 0
        self.submitted = []

    def headroom(self):
        return self._headroom - len(self.submitted)

    def submit(self, request):
        self.submitted.append(request)
        return request

    def stats(self):
        return {"group_id": self.group_id, "headroom": self.headroom()}


class TestShardGroupFleet:
    def test_routes_to_most_headroom_ties_to_lowest_id(self):
        g0, g1 = _StubGroup(0, 2), _StubGroup(1, 2)
        fleet = tps.ShardGroupFleet([g0, g1])
        for i in range(4):
            fleet.submit("r%d" % i)
        # tie -> g0, then g1 (more headroom), alternating to balance
        assert len(g0.submitted) == 2 and len(g1.submitted) == 2
        assert fleet.stats()["submitted"] == 4

    def test_prefers_drained_group(self):
        g0, g1 = _StubGroup(0, 1), _StubGroup(1, 5)
        fleet = tps.ShardGroupFleet([g0, g1])
        for i in range(5):
            fleet.submit(i)
        # g1 absorbs 4 until its headroom drops to g0's; the tie then
        # breaks to the lower group id
        assert len(g1.submitted) == 4
        assert len(g0.submitted) == 1

    def test_empty_fleet_rejected(self):
        with pytest.raises(ValueError):
            tps.ShardGroupFleet([])


# ------------------------------------------------------------ heavy drills
@pytest.mark.slow
class TestHeavy:
    def test_tp4_token_identity_and_comm_pin(self, lm, baseline):
        eng = make_engine(lm, tp=4)
        got = run_all(eng, mixed_requests(7))
        for i, (a, b) in enumerate(zip(baseline, got)):
            assert a == b, "request %d diverged" % i
        chk = eng.decode_hlo_comm_check()
        assert chk["count_match"] and chk["wire_match"], chk
        # tp=4 ring factor 2(N-1)/N = 1.5
        L, s, h = CFG.num_layers, eng.slots, CFG.hidden_size
        assert chk["comm_bytes_per_step"] == 1.5 * 2 * L * s * h * 4
        assert eng.stats()["executables"]["decode_step"] == 1

    def test_tp2_int8_kv_and_dense_identity(self, lm):
        # int8 KV: TP must match single-chip int8 (not f32) exactly
        base = make_engine(lm, kv_dtype="int8")
        ref = run_all(base, mixed_requests(5))
        eng = make_engine(lm, tp=2, kv_dtype="int8")
        got = run_all(eng, mixed_requests(5))
        assert ref == got
        # dense (non-paged) stacks shard over heads too
        dbase = gen.GenerationEngine(lm, slots=3, max_len=64,
                                     prefill_buckets=[8, 16],
                                     max_queue=64, paged=False)
        dref = run_all(dbase, mixed_requests(5))
        deng = tps.TPGenerationEngine(lm, tp=2, slots=3, max_len=64,
                                      prefill_buckets=[8, 16],
                                      max_queue=64, paged=False)
        dgot = run_all(deng, mixed_requests(5))
        assert dref == dgot
        assert deng.stats()["executables"]["prefill"] == {8: 1, 16: 1}

    def test_tp_decode_inside_disagg_group(self, lm, baseline):
        prefill = make_engine(lm, slots=2, kv_blocks=10)
        decode = make_engine(lm, tp=2, slots=3, kv_blocks=14)
        pair = tps.DisaggPair(prefill, decode, group_id=3)
        handles = [pair.submit(r) for r in mixed_requests(7)]
        pair.run_until_idle()
        got = [h.result(timeout=30.0) for h in handles]
        for i, (a, b) in enumerate(zip(baseline, got)):
            assert a == b, "request %d diverged" % i
        st = pair.stats()
        assert st["tp"]["degree"] == 2
        dex = decode.stats()["executables"]
        assert all(v == 0 for v in dex["prefill"].values())
