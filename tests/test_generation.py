"""`paddle_tpu.generation`: KV cache, decode kernel, sampling, the
continuous-batching engine's exactness vs the sequential oracle, and
its compile-once discipline.

The load-bearing drills:

* **exactness** — more requests than slots with mixed greedy/sampled
  policies and staggered finish times, so slots free and REFILL
  mid-flight; every token stream must equal the one-request-at-a-time
  oracle's, token for token, at fixed seeds;
* **compile-once** — after the executable set is built (one prefill
  per bucket + ONE decode step), further traffic compiles NOTHING
  (PR-4 compile-event accumulator) and the decode jit cache holds
  exactly one entry per engine config;
* **failure paths** — slot exhaustion sheds with Retry-After;
  over-long requests are refused up front.
"""

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import models
from paddle_tpu.fluid import dygraph
from paddle_tpu.generation import kv_cache

gen = paddle_tpu.generation

CFG = models.TransformerLMConfig.tiny()


@pytest.fixture(scope="module")
def lm():
    with dygraph.guard():
        np.random.seed(0)
        model = models.TransformerLM(CFG)
    return model


def make_engine(model, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_queue", 64)
    return gen.GenerationEngine(model, **kw)


def mixed_requests(n, max_new=6, stop=()):
    rng = np.random.RandomState(1)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(2, 14))
        prompt = rng.randint(0, CFG.vocab_size, plen)
        sp = (gen.SamplingParams.greedy() if i % 2 == 0 else
              gen.SamplingParams(temperature=0.9, top_k=20, top_p=0.9,
                                 seed=100 + i))
        reqs.append(gen.GenerationRequest(
            prompt, max_new_tokens=max_new + (i % 3), sampling=sp,
            stop_token_ids=stop, request_id="t%d" % i))
    return reqs


# ---------------------------------------------------------------------------
# decode-attention reference
# ---------------------------------------------------------------------------


class TestDecodeAttention:
    def _data(self, n=3, t=256, h=4, d=16, seed=0):
        rng = np.random.RandomState(seed)
        q = rng.randn(n, h, d).astype(np.float32)
        k = rng.randn(n, t, h, d).astype(np.float32)
        v = rng.randn(n, t, h, d).astype(np.float32)
        return q, k, v

    def test_reference_matches_plain_softmax(self):
        from paddle_tpu.ops.cached_attention import (
            decode_attention_reference,
        )
        import jax.numpy as jnp

        q, k, v = self._data()
        lens = jnp.asarray([5, 1, 200], jnp.int32)
        out = np.asarray(decode_attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lens))
        for n, L in enumerate([5, 1, 200]):
            s = np.einsum("hd,thd->ht", q[n], k[n, :L]) * 16 ** -0.5
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum("ht,thd->hd", p, v[n, :L])
            np.testing.assert_allclose(out[n], ref, rtol=1e-5,
                                       atol=1e-5)

    def test_empty_slot_emits_zeros(self):
        from paddle_tpu.ops.cached_attention import (
            decode_attention_reference,
        )
        import jax.numpy as jnp

        q, k, v = self._data(n=2)
        lens = jnp.asarray([0, 3], jnp.int32)
        out = np.asarray(decode_attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lens))
        assert np.all(out[0] == 0.0)
        assert np.any(out[1] != 0.0)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


class TestSampling:
    def _sample(self, logits, **kw):
        import jax.numpy as jnp

        n = logits.shape[0]
        keys = np.stack([gen.make_base_key(kw.get("seed", 0) + i)
                         for i in range(n)]).astype(np.uint32)
        return np.asarray(gen.sample_tokens(
            jnp.asarray(logits), jnp.asarray(keys),
            np.full(n, kw.get("step", 0), np.int32),
            np.full(n, kw.get("temperature", 1.0), np.float32),
            np.full(n, kw.get("top_k", 0), np.int32),
            np.full(n, kw.get("top_p", 1.0), np.float32)))

    def test_greedy_is_argmax(self):
        rng = np.random.RandomState(0)
        logits = rng.randn(4, 33).astype(np.float32)
        got = self._sample(logits, temperature=0.0)
        np.testing.assert_array_equal(got, logits.argmax(-1))

    def test_top_k_restricts_support(self):
        rng = np.random.RandomState(1)
        logits = rng.randn(64, 50).astype(np.float32)
        got = self._sample(logits, temperature=1.0, top_k=3, seed=5)
        top3 = np.argsort(-logits, axis=-1)[:, :3]
        for i, t in enumerate(got):
            assert t in top3[i]

    def test_top_p_always_keeps_argmax(self):
        rng = np.random.RandomState(2)
        logits = rng.randn(32, 40).astype(np.float32)
        got = self._sample(logits, temperature=1.0, top_p=1e-9, seed=7)
        np.testing.assert_array_equal(got, logits.argmax(-1))

    def test_stream_is_slot_position_independent(self):
        """The same (seed, step, logits) samples the same token in any
        row — the property engine-vs-oracle exactness rests on."""
        import jax.numpy as jnp

        rng = np.random.RandomState(3)
        row = rng.randn(17).astype(np.float32)
        key = gen.make_base_key(42).astype(np.uint32)
        outs = []
        for pos, n in ((0, 1), (2, 4), (5, 8)):
            logits = rng.randn(n, 17).astype(np.float32)
            logits[pos] = row
            keys = rng.randint(0, 2 ** 31, (n, 2)).astype(np.uint32)
            keys[pos] = key
            got = np.asarray(gen.sample_tokens(
                jnp.asarray(logits), jnp.asarray(keys),
                np.full(n, 3, np.int32), np.full(n, 0.8, np.float32),
                np.full(n, 10, np.int32), np.full(n, 0.95, np.float32)))
            outs.append(int(got[pos]))
        assert len(set(outs)) == 1


def sort_sampler(logits, keys, steps, temperature, top_k, top_p):
    """The plain reference: the sampler as it stood before PR 32, which
    sorts the vocabulary once for the top-k cut and again for top-p.
    Returns what top-k left, what top-p then left (dropped entries at
    -1e30) and the tokens."""
    import jax
    import jax.numpy as jnp

    neg = -1e30
    logits = jnp.asarray(logits).astype(jnp.float32)
    n, v = logits.shape
    greedy = temperature <= 0.0
    safe_t = jnp.where(greedy, 1.0, temperature)
    scaled = logits / safe_t[:, None]

    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    after_k = jnp.where((top_k > 0)[:, None] & (scaled < kth), neg, scaled)

    sorted2 = jnp.sort(after_k, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]      # mass BEFORE the token
    keep = keep.at[:, 0].set(True)             # argmax always survives
    thresh = jnp.min(jnp.where(keep, sorted2, jnp.inf), axis=-1)
    after_p = jnp.where(
        (top_p < 1.0)[:, None] & (after_k < thresh[:, None]), neg, after_k)

    step_keys = jax.vmap(jax.random.fold_in)(keys, steps)
    sampled = jax.vmap(jax.random.categorical)(step_keys, after_p)
    tokens = jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)
    return after_k, after_p, tokens.astype(jnp.int32)


def selection_sampler(logits, keys, steps, temperature, top_k, top_p):
    """The same three results from the sampler under test."""
    import jax.numpy as jnp

    from paddle_tpu.generation import sampling

    samples = temperature > 0.0
    scaled = (jnp.asarray(logits).astype(jnp.float32)
              / jnp.where(samples, temperature, 1.0)[:, None])
    return (*sampling._cut(scaled, samples, top_k, top_p),
            sampling.sample_tokens(logits, keys, steps, temperature,
                                   top_k, top_p))


def grid_policies(v):
    """(temperature, top_k, top_p) of the grid's 16 rows; row 14's
    logits are all equal."""
    return [(0.0, 0, 1.0), (0.0, 3, 0.5), (0.7, 0, 1.0), (1.0, 1, 1.0),
            (1.0, 3, 1.0), (1.0, 40, 1.0), (1.0, v, 1.0), (1.0, v + 5, 1.0),
            (1.0, 0, 1e-9), (1.0, 0, 0.5), (1.0, 0, 0.95),
            (0.8, 40, 0.95), (1.3, 3, 0.5), (1.0, v + 5, 0.95),
            (1.0, 3, 0.5), (0.8, 40, 0.0)]


def compare_samplers(v, n, dtype, seed=0):
    """The selection sampler against the sort sampler on the grid's 16
    rows, ``n`` of them a call, with continuous logits and with logits
    rounded to 1/4 (ties).  Asserts: the top-k survivors equal, ties
    included; the sets top-p keeps equal but for tokens whose mass of
    strictly larger survivors is within 1e-5 of ``top_p``; the tokens
    equal wherever those sets are.  Returns how many rows had such a
    token."""
    import jax
    import jax.numpy as jnp

    policies = grid_policies(v)
    rng = np.random.RandomState(seed)
    old, new = jax.jit(sort_sampler), jax.jit(selection_sampler)
    boundary_rows = 0
    for tied in (False, True):
        logits = 3.0 * rng.randn(len(policies), v)
        if tied:
            logits = np.round(logits * 4) / 4
        logits[14] = 0.5
        logits = jnp.asarray(logits.astype(np.float32)).astype(dtype)
        keys = rng.randint(0, 2 ** 31, (len(policies), 2)).astype(np.uint32)
        steps = rng.randint(0, 200, len(policies)).astype(np.int32)
        temp, top_k, top_p = (
            np.asarray(c, t) for c, t in zip(
                zip(*policies), (np.float32, np.int32, np.float32)))
        for lo in range(0, len(policies), n):
            rows = slice(lo, lo + n)
            args = (logits[rows], keys[rows], steps[rows], temp[rows],
                    top_k[rows], top_p[rows])
            want = [np.asarray(a) for a in old(*args)]
            got = [np.asarray(a) for a in new(*args)]
            for r in range(n):
                where = (v, n, str(dtype), tied, lo + r)
                if temp[lo + r] <= 0.0:
                    assert got[2][r] == want[2][r] == np.argmax(
                        np.asarray(logits[lo + r], np.float32)), where
                    continue
                np.testing.assert_array_equal(
                    got[0][r], want[0][r], err_msg=str(where))
                differs = np.nonzero(got[1][r] != want[1][r])[0]
                if len(differs):
                    boundary_rows += 1
                    vals = want[0][r].astype(np.float64)
                    live = vals > -1e29
                    p = np.where(live, np.exp(vals - vals[live].max()), 0.0)
                    p /= p.sum()
                    for j in differs:
                        before = p[vals > vals[j]].sum()
                        assert abs(before - top_p[lo + r]) <= 1e-5, (
                            where, j, before)
                else:
                    assert got[2][r] == want[2][r], where
    return boundary_rows


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [1, 16])
@pytest.mark.parametrize("v", [33, 1000, 50257])
def test_selection_sampler_equals_the_sort_sampler(v, n, dtype):
    compare_samplers(v, n, dtype)


def test_select_is_the_kth_largest_and_the_mass_cut():
    """`_select` alone, against numpy: the k-th largest of a row for any
    k, negative values, both zeros and ties included, and the largest
    key whose mass at or above reaches a target; no round for a call
    whose rows are all off."""
    import jax.numpy as jnp

    from paddle_tpu.generation import sampling

    rng = np.random.RandomState(7)
    x = np.round(rng.randn(6, 97) * 8).astype(np.float32) / 4
    x[0, :5] = [0.0, -0.0, 0.0, -0.0, 1e-38]
    x[1] = -np.abs(x[1]) - 1.0
    x[2] = 2.5
    keys = sampling._ordered(jnp.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(sampling._unordered(keys)).view(np.uint32),
        x.view(np.uint32))
    on = np.ones(6, bool)
    for k in (1, 2, 17, 96, 97):
        got = np.asarray(sampling._unordered(sampling._select(
            keys, None, np.full(6, k, np.int32), on)))
        np.testing.assert_array_equal(got, np.sort(x, axis=-1)[:, -k])
    w = rng.rand(6, 97).astype(np.float32)
    target = np.full(6, 0.3 * 97 / 2, np.float32)
    got = np.asarray(sampling._unordered(sampling._select(
        keys, jnp.asarray(w), target, on)))
    for r in range(6):
        reach = [t for t in np.unique(x[r])
                 if w[r][x[r] >= t].sum(dtype=np.float64) >= target[r]]
        assert got[r] == max(reach)
    off = np.asarray(sampling._select(keys, None, np.ones(6, np.int32),
                                      np.zeros(6, bool)))
    np.testing.assert_array_equal(off, np.zeros(6, np.uint32))


# ---------------------------------------------------------------------------
# model: decode path == full forward
# ---------------------------------------------------------------------------


class TestTransformerLM:
    def test_prefill_equals_plain_forward(self, lm):
        from paddle_tpu.fluid import framework

        rng = np.random.RandomState(0)
        ids = rng.randint(0, CFG.vocab_size, (2, 8)).astype(np.int64)
        pos = np.tile(np.arange(8, dtype=np.int64), (2, 1))
        with dygraph.guard():
            framework._dygraph_tracer.train_mode = False
            for vb in lm.state_dict().values():
                framework._dygraph_tracer.register_var(vb)
            full = lm(dygraph.to_variable(ids),
                      dygraph.to_variable(pos)).numpy()
            pf, kvs = lm(dygraph.to_variable(ids),
                         dygraph.to_variable(pos), use_cache=True)
        np.testing.assert_array_equal(pf.numpy(), full)
        assert len(kvs) == CFG.num_layers
        assert np.asarray(kvs[0][0]).shape == (
            2, 8, CFG.num_heads, CFG.head_dim)

    def test_decode_step_equals_full_forward_last_position(self, lm):
        import jax.numpy as jnp

        from paddle_tpu.fluid import framework
        from paddle_tpu.generation.kv_cache import (
            flatten_layers,
            group_layers,
        )

        rng = np.random.RandomState(0)
        B, S, T = 2, 8, 16
        L, H, Dh = CFG.num_layers, CFG.num_heads, CFG.head_dim
        ids = rng.randint(0, CFG.vocab_size, (B, S)).astype(np.int64)
        pos = np.tile(np.arange(S, dtype=np.int64), (B, 1))
        with dygraph.guard():
            framework._dygraph_tracer.train_mode = False
            for vb in lm.state_dict().values():
                framework._dygraph_tracer.register_var(vb)
            full = lm(dygraph.to_variable(ids),
                      dygraph.to_variable(pos)).numpy()
            _, kvs = lm(dygraph.to_variable(ids[:, :S - 1]),
                        dygraph.to_variable(pos[:, :S - 1]),
                        use_cache=True)
            # the cache's form: one [B, T, H*Dh] array per layer, K's
            # over the layers and then V's (`KVCache.arrays`)
            cache = gen.KVCache(L, B, T, H, Dh)
            arrays = [np.zeros(cache.layer_shape, np.float32)
                      for _ in range(2 * L)]
            for li, (k, v) in enumerate(kvs):
                arrays[li][:, :S - 1] = np.asarray(k).reshape(B, S - 1, -1)
                arrays[L + li][:, :S - 1] = np.asarray(v).reshape(
                    B, S - 1, -1)
            # the forward takes each layer's arrays as one tuple
            layers = group_layers([jnp.asarray(a) for a in arrays], L)
            assert [len(mine) for mine in layers] == [2] * L
            logits, out = lm(
                dygraph.to_variable(ids[:, S - 1:S]),
                dygraph.to_variable(np.full((B, 1), S - 1, np.int64)),
                caches=layers,
                cache_positions=jnp.asarray([S - 1] * B))
            assert [len(mine) for mine in out] == [2] * L
            out = flatten_layers(out)
        # the cached path IS the full math at the last row, but not its
        # summation order: the decode step contracts a [1, D] query row
        # where the full forward contracts [S, D], and the CPU backend
        # blocks the two matmuls differently — observed 6e-8 apart, so the
        # pin is a float32 rounding bound, not bit equality
        np.testing.assert_allclose(logits.numpy()[:, 0], full[:, -1],
                                   rtol=0, atol=1e-6)
        # and the step wrote this token's K/V at position S-1 of every
        # layer's own arrays, and nothing else
        assert len(out) == 2 * L
        for before, after in zip(arrays, out):
            after = np.asarray(after)
            assert after.shape == cache.layer_shape
            assert np.all(after[:, S - 1] != 0)
            np.testing.assert_array_equal(after[:, :S - 1],
                                          before[:, :S - 1])
            assert not after[:, S:].any()


# ---------------------------------------------------------------------------
# engine: exactness, continuous batching, compile-once, failure paths
# ---------------------------------------------------------------------------


class TestEngine:
    def test_exact_vs_sequential_oracle_with_midflight_refill(self, lm):
        reqs = mixed_requests(7)
        eng = make_engine(lm)
        handles = [eng.submit(r) for r in reqs]
        refilled = False
        seen_busy = False
        while eng.step():
            occ = eng.occupancy()
            if occ["free"] == 0 and occ["pending"] > 0:
                seen_busy = True
            if seen_busy and occ["pending"] < len(reqs) - eng.slots:
                refilled = True
        got = [h.result() for h in handles]
        # 7 requests over 3 slots with staggered max_new: slots MUST
        # have freed and refilled while others kept decoding
        assert refilled or len(reqs) > eng.slots
        oracle = gen.sequential_oracle(lambda: make_engine(lm), reqs)
        assert got == oracle
        # mixed policies actually exercised both samplers
        assert any(r.sampling.temperature == 0 for r in reqs)
        assert any(r.sampling.temperature > 0 for r in reqs)

    def test_stop_token_ends_stream(self, lm):
        # greedy-decode once to learn the first emitted token, then use
        # it as the stop token — deterministic stop mid-stream
        probe = make_engine(lm)
        h = probe.submit(gen.GenerationRequest([5, 7, 9],
                                               max_new_tokens=6))
        probe.run_until_idle()
        first = h.result()[0]
        eng = make_engine(lm)
        h2 = eng.submit(gen.GenerationRequest(
            [5, 7, 9], max_new_tokens=6, stop_token_ids=(first,)))
        eng.run_until_idle()
        assert h2.result() == [first]
        assert h2.finish_reason == "stop_token"

    def test_compile_once_per_config(self, lm):
        from paddle_tpu.observability import install_jax_compile_hooks
        from paddle_tpu.observability.metrics import default_registry

        install_jax_compile_hooks()
        ctr = default_registry().counter(
            "xla_compilations_total",
            "XLA backend compilations (jax.monitoring)")
        eng = make_engine(lm)
        # build the whole executable set: both buckets + the decode step
        warm = [gen.GenerationRequest(list(range(1, b + 1)),
                                      max_new_tokens=2)
                for b in eng.prefill_buckets]
        for r in warm:
            eng.submit(r)
        eng.run_until_idle()
        c0 = ctr.value
        for r in mixed_requests(6, max_new=4):
            eng.submit(r)
        eng.run_until_idle()
        assert ctr.value == c0, (
            "traffic after warmup compiled %d executables; the decode "
            "loop must compile once per config" % (ctr.value - c0))
        assert eng._decode_cache_size() == 1

    def test_slot_exhaustion_sheds_with_retry_after(self, lm):
        from paddle_tpu.serving.admission import ShedError

        eng = make_engine(lm, slots=1, max_queue=2)
        for i in range(2):   # queue fills (slots claim at step time)
            eng.submit(gen.GenerationRequest([1, 2, 3],
                                             max_new_tokens=4))
        with pytest.raises(ShedError) as ei:
            eng.submit(gen.GenerationRequest([1, 2, 3],
                                             max_new_tokens=4))
        assert ei.value.reason == "slots_full"
        assert ei.value.retry_after_s >= 1
        eng.run_until_idle()

    def test_over_long_requests_refused(self, lm):
        eng = make_engine(lm)
        with pytest.raises(ValueError):
            eng.submit(gen.GenerationRequest(list(range(17)),
                                             max_new_tokens=2))
        with pytest.raises(ValueError):
            eng.submit(gen.GenerationRequest([1, 2],
                                             max_new_tokens=100))

    def test_background_thread_mode(self, lm):
        eng = make_engine(lm).start()
        try:
            handles = [eng.submit(r) for r in mixed_requests(4)]
            got = [h.result(timeout=60) for h in handles]
            assert all(len(g) > 0 for g in got)
        finally:
            eng.stop()

    def test_occupancy_and_stats(self, lm):
        eng = make_engine(lm)
        assert eng.occupancy() == {"slots": 3, "active": 0, "free": 3,
                                   "pending": 0, "chunking": 0}
        st = eng.stats()
        assert st["decode_executables"] in (0, 1)
        assert st["cache"]["bytes"] == eng.cache.nbytes
        assert st["cache"]["paged"] is True


# ---------------------------------------------------------------------------
# kv cache / cost model / tune
# ---------------------------------------------------------------------------


def test_kv_cache_shape_and_bytes():
    c = gen.KVCache(num_layers=2, slots=3, max_len=64, num_heads=4,
                    head_dim=8)
    # one array per layer for K and for V, heads merged into the last
    # dimension
    assert c.layer_shape == (3, 64, 32)
    assert len(c.arrays()) == 2 * 2
    assert all(a.shape == c.layer_shape for a in c.arrays())
    assert c.nbytes == 2 * 2 * 3 * 64 * 4 * 8 * 4
    d = c.describe()
    assert d["bytes"] == c.nbytes == sum(a.nbytes for a in c.arrays())
    assert d["dtype"] == "float32"


def test_decode_step_cost_units():
    from paddle_tpu.analysis.perf import ChipSpec, decode_step_cost

    chip = ChipSpec("test", 100e12, 100e9)
    c = decode_step_cost(num_layers=2, hidden_size=64, num_heads=4,
                         vocab_size=100, intermediate_size=128,
                         slots=4, cache_len=32, chip=chip)
    assert c.kv_read_bytes == 2 * 2 * 4 * 32 * 64 * 4
    params = 2 * (4 * 64 * 64 + 2 * 64 * 128) + 100 * 64
    assert c.param_read_bytes == params * 4
    assert c.bound == "memory"
    assert c.tokens_per_s > 0
    assert c.to_dict()["schema_version"] == 1


def test_tune_generation_slot_search():
    from paddle_tpu import tune
    from paddle_tpu.tune.space import generation_config_candidates

    cands = generation_config_candidates(
        slot_counts=(4, 8, 16), max_len=128,
        hbm_budget_bytes=10 * 2 ** 20, cache_bytes_per_slot=2 ** 20)
    assert [c.label for c in cands] == ["slots4", "slots8"]  # 16 pruned
    assert cands[0].params == {"slots": 4, "max_len": 128}

    timings = {4: 0.010, 8: 0.004}
    report = tune.search_generation_config(
        lambda p: timings[p["slots"]], workload="test-gen-search",
        slot_counts=(4, 8), max_len=128, use_cache=False)
    assert report.winner.candidate.label == "slots8"
    assert report.default_s == pytest.approx(0.010)

    with pytest.raises(ValueError):
        tune.search_generation_config(
            lambda p: 1.0, workload="none", slot_counts=(64,),
            hbm_budget_bytes=1, cache_bytes_per_slot=2 ** 30)


# ---------------------------------------------------------------------------
# per-token logprobs (opt-in) + in-place weight hot-swap
# ---------------------------------------------------------------------------


class TestLogprobsAndSwap:
    def test_logprobs_match_full_forward_rescore(self, lm):
        """Engine logprobs are log-softmax of the RAW logits at the
        sampled token — verified against a full causal forward over
        (prompt + generation), the `rl.ReferenceScorer` semantics."""
        import jax.numpy as jnp

        from paddle_tpu.fluid import framework
        from paddle_tpu.generation.sampling import token_logprobs

        eng = make_engine(lm, logprobs=True)
        req = gen.GenerationRequest(
            [3, 1, 4, 1, 5], max_new_tokens=5,
            sampling=gen.SamplingParams(temperature=0.8, top_k=10,
                                        seed=77))
        h = eng.submit(req)
        eng.run_until_idle()
        toks, lps = h.result(), h.logprobs()
        assert len(lps) == len(toks) and all(lp <= 0.0 for lp in lps)

        seq = req.prompt_ids + toks
        with dygraph.guard():
            framework._dygraph_tracer.train_mode = False
            for vb in lm.state_dict().values():
                framework._dygraph_tracer.register_var(vb)
            ids = np.asarray(seq[:-1], np.int64)[None]
            pos = np.arange(len(seq) - 1, dtype=np.int64)[None]
            logits = lm(dygraph.to_variable(ids),
                        dygraph.to_variable(pos))
        ref = np.asarray(token_logprobs(
            jnp.asarray(logits.data)[0],
            jnp.asarray(seq[1:], jnp.int32)))
        g0 = len(req.prompt_ids) - 1
        np.testing.assert_allclose(lps, ref[g0:g0 + len(toks)],
                                   rtol=2e-4, atol=2e-4)

    def test_disabled_engine_streams_are_byte_identical(self, lm):
        """logprobs=False (the default) is the pre-logprob engine to
        the byte: 3-tuple token events, empty handle.logprobs(), and
        the SAME tokens as a logprob engine at the same seeds."""
        reqs = mixed_requests(4)
        plain = make_engine(lm)
        with_lp = make_engine(lm, logprobs=True)
        ev_plain, out_plain, out_lp = [], [], []
        for r in reqs:
            h = plain.submit(gen.GenerationRequest(
                r.prompt_ids, max_new_tokens=r.max_new_tokens,
                sampling=r.sampling))
            plain.run_until_idle()
            ev_plain.extend(e for e in h.events(timeout=5.0)
                            if e[0] == "token")
            out_plain.append(h.result())
            assert h.logprobs() == []
        for r in reqs:
            h = with_lp.submit(gen.GenerationRequest(
                r.prompt_ids, max_new_tokens=r.max_new_tokens,
                sampling=r.sampling))
            with_lp.run_until_idle()
            out_lp.append(h.result())
            assert len(h.logprobs()) == len(out_lp[-1])
        assert all(len(e) == 3 for e in ev_plain)
        assert out_plain == out_lp

    def test_swap_params_serves_new_weights_without_recompile(self, lm):
        """Hot-swap: same shapes -> zero new executables, next request
        decodes under the new weights; name/shape mismatches refused."""
        eng = make_engine(lm, logprobs=True)
        req = lambda: gen.GenerationRequest([2, 7, 1, 8], max_new_tokens=4)
        h0 = eng.submit(req())
        eng.run_until_idle()
        before = h0.result()
        snap = eng.snapshot_params()

        rng = np.random.RandomState(123)
        bumped = {k: (v + rng.normal(scale=0.5, size=v.shape)
                      .astype(v.dtype) if v.ndim >= 2 else v)
                  for k, v in snap.items()}
        eng.swap_params(bumped)
        h1 = eng.submit(req())
        eng.run_until_idle()
        after = h1.result()
        assert eng._decode_cache_size() == 1
        assert after != before          # tiny-vocab greedy path moved

        eng.swap_params(snap)           # rollback restores the stream
        h2 = eng.submit(req())
        eng.run_until_idle()
        assert h2.result() == before

        with pytest.raises(ValueError):
            eng.swap_params({k: v for k, v in snap.items()
                             if k != "word.weight"})
        bad = dict(snap)
        name = next(k for k in bad if bad[k].ndim == 2)
        bad[name] = bad[name][:, :-1]
        with pytest.raises(ValueError):
            eng.swap_params(bad)


# ---------------------------------------------------------------------------
# paged KV: kernels, block pool, prefix cache (PR-17)
# ---------------------------------------------------------------------------


class TestPagedKernels:
    def _pool_from_dense(self, k, v, bs, extra_blocks=2, seed=3):
        """Scatter a dense [N, T, H, D] cache into a PERMUTED block
        pool + table — paged reads must be layout-independent."""
        rng = np.random.RandomState(seed)
        n, t, h, d = k.shape
        nb_per = t // bs
        num_blocks = 1 + n * nb_per + extra_blocks
        perm = 1 + rng.permutation(num_blocks - 1)[: n * nb_per]
        k_pool = np.zeros((num_blocks, bs, h, d), np.float32)
        v_pool = np.zeros((num_blocks, bs, h, d), np.float32)
        tables = np.zeros((n, nb_per), np.int32)
        for i in range(n):
            for j in range(nb_per):
                b = perm[i * nb_per + j]
                tables[i, j] = b
                k_pool[b] = k[i, j * bs:(j + 1) * bs]
                v_pool[b] = v[i, j * bs:(j + 1) * bs]
        return k_pool, v_pool, tables

    def test_paged_reference_matches_dense_reference(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.cached_attention import (
            decode_attention_reference,
            paged_decode_attention_reference,
        )

        rng = np.random.RandomState(0)
        n, t, h, d, bs = 3, 64, 4, 16, 16
        q = rng.randn(n, h, d).astype(np.float32)
        k = rng.randn(n, t, h, d).astype(np.float32)
        v = rng.randn(n, t, h, d).astype(np.float32)
        lens = jnp.asarray([5, 0, 64], jnp.int32)
        dense = decode_attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lens)
        k_pool, v_pool, tables = self._pool_from_dense(k, v, bs)
        paged = paged_decode_attention_reference(
            jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), lens)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                                   rtol=1e-6, atol=1e-6)

    def test_chunked_reference_c1_equals_decode_reference(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.cached_attention import (
            chunked_attention_reference,
            decode_attention_reference,
        )

        rng = np.random.RandomState(2)
        n, t, h, d = 3, 32, 4, 16
        q = rng.randn(n, 1, h, d).astype(np.float32)
        k = rng.randn(n, t, h, d).astype(np.float32)
        v = rng.randn(n, t, h, d).astype(np.float32)
        lens = np.asarray([7, 1, 32], np.int32)
        # decode contract: row 0 sits at position len-1 (its K/V is in)
        chunk = chunked_attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(lens - 1))
        dec = decode_attention_reference(
            jnp.asarray(q[:, 0]), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(lens))
        np.testing.assert_allclose(np.asarray(chunk)[:, 0],
                                   np.asarray(dec), rtol=1e-5,
                                   atol=1e-6)

    def test_chunked_reference_per_row_causal_mask(self):
        """Row i attends exactly t <= start + i — against a literal
        per-row numpy softmax."""
        import jax.numpy as jnp

        from paddle_tpu.ops.cached_attention import (
            chunked_attention_reference,
        )

        rng = np.random.RandomState(3)
        n, c, t, h, d = 2, 3, 16, 2, 8
        q = rng.randn(n, c, h, d).astype(np.float32)
        k = rng.randn(n, t, h, d).astype(np.float32)
        v = rng.randn(n, t, h, d).astype(np.float32)
        start = np.asarray([4, 0], np.int32)
        out = np.asarray(chunked_attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(start)))
        for i in range(n):
            for ci in range(c):
                lim = start[i] + ci + 1
                s = np.einsum("hd,thd->ht", q[i, ci],
                              k[i, :lim]) * d ** -0.5
                p = np.exp(s - s.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                ref = np.einsum("ht,thd->hd", p, v[i, :lim])
                np.testing.assert_allclose(out[i, ci], ref, rtol=1e-5,
                                           atol=1e-5)

    @pytest.mark.parametrize("c,h", [(1, 4), (3, 4), (40, 4)],
                             ids=["decode", "verify", "wide-chunk"])
    def test_merged_attention_equals_the_split_heads_reference(self, c, h):
        """What the cached forward runs over a cache held with its heads
        merged (block-diagonal queries while C*H fits the MXU's 128
        rows, the view's heads split beyond) is the split-heads
        reference's math, dead rows and empty slots included."""
        import jax.numpy as jnp

        from paddle_tpu.ops.cached_attention import (
            chunked_attention_reference,
            merged_attention,
        )

        rng = np.random.RandomState(5)
        n, t, d = 3, 48, 8
        q = rng.randn(n, c, h, d).astype(np.float32)
        k = rng.randn(n, t, h, d).astype(np.float32)
        v = rng.randn(n, t, h, d).astype(np.float32)
        start = jnp.asarray([5, -1, t - c], jnp.int32)   # slot 1: empty
        want = np.asarray(chunked_attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), start))
        got = np.asarray(merged_attention(
            jnp.asarray(q), jnp.asarray(k.reshape(n, t, h * d)),
            jnp.asarray(v.reshape(n, t, h * d)), start))
        assert got.shape == (n, c, h, d)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert not got[1, 0].any()                       # nothing live

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["float32", "int8"])
    def test_kv_write_scatters_rows_into_each_array(self, quantized):
        """The one cache write: row r lands at [i0[r], i1[r]] of every
        array of the layer (heads merged; int8 rows quantized on the
        way in, with their scales), and nothing else moves."""
        import jax.numpy as jnp

        from paddle_tpu.ops.cached_attention import (
            dequantize_kv,
            kv_write,
        )

        rng = np.random.RandomState(6)
        a, b, h, d, r = 5, 4, 2, 8, 3
        k_rows = rng.randn(r, h, d).astype(np.float32)
        v_rows = rng.randn(r, h, d).astype(np.float32)
        i0 = jnp.asarray([4, 0, 2], jnp.int32)
        i1 = jnp.asarray([1, 3, 0], jnp.int32)
        store = jnp.int8 if quantized else jnp.float32
        arrays = (jnp.zeros((a, b, h * d), store),
                  jnp.zeros((a, b, h * d), store))
        if quantized:
            arrays += (jnp.zeros((a, b, h), jnp.float32),) * 2
        out = kv_write(arrays, i0, i1, jnp.asarray(k_rows),
                       jnp.asarray(v_rows))
        assert [(o.shape, o.dtype) for o in out] == [
            (x.shape, x.dtype) for x in arrays]
        for j, rows in enumerate((k_rows, v_rows)):
            got = np.array(out[j])
            if quantized:
                got = np.array(dequantize_kv(
                    jnp.asarray(got).reshape(a, b, h, d), out[2 + j]))
                got = got.reshape(a, b, h * d)
            written = got[np.asarray(i0), np.asarray(i1)]
            np.testing.assert_allclose(
                written, rows.reshape(r, h * d),
                atol=0.02 if quantized else 0)
            got[np.asarray(i0), np.asarray(i1)] = 0
            assert not got.any()

    def test_cached_attention_dense_and_paged_agree(self):
        """One layer's write-then-attend through a dense cache and
        through a permuted block pool: the same context, each new row
        in its own place, for one token a slot and for three."""
        import jax.numpy as jnp

        from paddle_tpu.ops.cached_attention import (
            cached_attention,
            paged_gather_kv,
        )

        rng = np.random.RandomState(7)
        n, t, h, d, bs = 3, 32, 4, 8, 8
        k = rng.randn(n, t, h, d).astype(np.float32)
        v = rng.randn(n, t, h, d).astype(np.float32)
        k_pool, v_pool, tables = self._pool_from_dense(k, v, bs)
        merged = lambda x: jnp.asarray(x.reshape(x.shape[:2] + (h * d,)))
        pos = jnp.asarray([4, 0, 20], jnp.int32)
        for c in (1, 3):
            q, k_new, v_new = (jnp.asarray(
                rng.randn(n, c, h, d).astype(np.float32)) for _ in range(3))
            dense, (dk, dv) = cached_attention(
                q, k_new, v_new, (merged(k), merged(v), pos))
            paged, (pk, pv) = cached_attention(
                q, k_new, v_new, (merged(k_pool), merged(v_pool), pos,
                                  jnp.asarray(tables), bs))
            np.testing.assert_allclose(np.asarray(paged),
                                       np.asarray(dense),
                                       rtol=1e-5, atol=1e-6)
            assert dk.shape == (n, t, h * d)
            assert pk.shape == merged(k_pool).shape
            for i in range(n):
                rows = slice(int(pos[i]), int(pos[i]) + c)
                np.testing.assert_array_equal(
                    np.asarray(dk)[i, rows],
                    np.asarray(k_new)[i].reshape(c, h * d))
            np.testing.assert_array_equal(
                np.asarray(paged_gather_kv(pv, jnp.asarray(tables))),
                np.asarray(dv))

    def test_int8_roundtrip_and_zero_rows(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.cached_attention import (
            dequantize_kv,
            quantize_kv,
        )

        rng = np.random.RandomState(4)
        x = rng.randn(5, 3, 4, 16).astype(np.float32)
        q, s = quantize_kv(jnp.asarray(x))
        assert np.asarray(q).dtype == np.int8
        back = np.asarray(dequantize_kv(q, s))
        # symmetric 127-level quantization: error <= scale/2 per elem
        amax = np.abs(x).max(-1, keepdims=True)
        assert np.all(np.abs(back - x) <= amax / 127.0 + 1e-7)
        z, zs = quantize_kv(jnp.zeros((2, 4, 8)))
        assert np.all(np.asarray(dequantize_kv(z, zs)) == 0.0)


class TestBlockPool:
    def test_alloc_free_refcount_discipline(self):
        pool = gen.BlockPool(6)
        assert pool.free_blocks == 5 and pool.used_blocks == 0
        a = pool.alloc(3)
        assert sorted(a) == [1, 2, 3]      # lowest-id-first, 0 reserved
        assert pool.used_blocks == 3
        pool.incref([a[0]])                # shared block: two users now
        assert pool.refcount(a[0]) == 2
        freed = pool.decref(a)             # first user lets go of all
        assert freed == a[1:]              # shared block NOT freed
        assert pool.refcount(a[0]) == 1
        assert pool.decref([a[0]]) == [a[0]]   # last user -> freed
        assert pool.used_blocks == 0

    def test_exhaustion_and_misuse_raise(self):
        pool = gen.BlockPool(4)
        pool.alloc(3)
        with pytest.raises(gen.PoolExhausted):
            pool.alloc(1)
        with pytest.raises(ValueError):
            pool.decref([0])               # garbage block is pinned
        pool.decref([3])
        with pytest.raises(ValueError):
            pool.decref([3])               # double free
        with pytest.raises(ValueError):
            pool.incref([3])               # incref on a free block
        with pytest.raises(ValueError):
            gen.BlockPool(1)

    def test_freed_block_is_reused_lowest_first(self):
        pool = gen.BlockPool(5)
        a = pool.alloc(4)
        pool.decref([a[1]])
        assert pool.alloc(1) == [a[1]]


class TestPrefixCache:
    def _pc(self, num_blocks=10, bs=4):
        pool = gen.BlockPool(num_blocks)
        return pool, gen.PrefixCache(pool, bs)

    def test_register_lookup_and_cap(self):
        pool, pc = self._pc()
        prompt = list(range(100, 112))          # 3 full blocks of 4
        blocks = pool.alloc(3)
        pc.register(prompt, blocks)
        assert len(pc) == 3
        # registry holds its own reference on top of the slot's
        assert all(pool.refcount(b) == 2 for b in blocks)
        n, got = pc.lookup(prompt)
        # capped one token short of the prompt: 11 usable -> 2 blocks
        assert n == 8 and got == blocks[:2]
        assert all(pool.refcount(b) == 3 for b in blocks[:2])
        n2, got2 = pc.lookup(prompt[:4] + [999] * 8)   # diverges at b1
        assert n2 == 4 and got2 == blocks[:1]
        assert pc.lookup([1, 2, 3])[0] == 0            # sub-block miss
        st = pc.stats()
        assert st["hits"] == 2 and st["misses"] == 1
        assert st["hit_tokens"] == 12

    def test_shared_block_frees_only_at_refcount_zero(self):
        pool, pc = self._pc()
        prompt = list(range(8))
        mine = pool.alloc(2)
        pc.register(prompt, mine)
        pool.decref(mine)                  # slot releases -> registry holds
        assert all(pool.refcount(b) == 1 for b in mine)
        assert pool.used_blocks == 2       # STILL allocated (cache)
        n, shared = pc.lookup(prompt + [7])
        assert n == 8 and pool.refcount(shared[0]) == 2
        # eviction cannot touch blocks with outside users
        assert pc.evict(pool.num_blocks) == 0
        assert pool.used_blocks == 2
        pool.decref(shared)                # user done
        freed = pc.evict(pool.num_blocks - 1)
        assert freed == 2 and pool.used_blocks == 0
        assert len(pc) == 0

    def test_evict_is_lru_leaf_first(self):
        pool, pc = self._pc(num_blocks=4)       # 3 usable blocks
        old = pool.alloc(1)
        new = pool.alloc(1)
        pc.register(list(range(4)), old)        # registered earlier
        pc.register(list(range(50, 54)), new)
        pool.decref(old + new)                  # registry refs only
        # touch `new` so `old` is the LRU chain
        n, got = pc.lookup(list(range(50, 55)))
        assert n == 4
        pool.decref(got)
        # pressure for 2 free (1 free now): exactly the LRU chain goes
        assert pc.evict(2) == 1
        assert pc.lookup(list(range(4)) + [9])[0] == 0     # old gone
        n2, got2 = pc.lookup(list(range(50, 55)))          # new kept
        assert n2 == 4
        pool.decref(got2)


def test_paged_kv_cache_shapes_bytes_and_tables():
    c = gen.PagedKVCache(num_layers=2, num_blocks=9, block_size=16,
                         num_heads=4, head_dim=8, slots=3, max_len=64)
    # 2 * L arrays (K's over the layers, then V's), each one layer's
    # pool with the heads merged into the last dimension
    assert c.layer_shape == (9, 16, 32)
    assert len(c.arrays()) == 2 * 2
    assert all(a.shape == c.layer_shape and a.dtype == np.float32
               for a in c.arrays())
    assert c.nbytes == 2 * 2 * 9 * 16 * 4 * 8 * 4
    assert c.capacity_tokens == 8 * 16
    assert c.blocks_for(17) == 2
    b = c.pool.alloc(2)
    c.assign(0, 0, b[0])
    c.assign(0, 1, b[1])
    assert list(c.table_row(0)[:2]) == b
    c.clear_slot(0)
    assert np.all(c.table_row(0) == 0)
    d = c.describe()
    assert d["paged"] is True and d["kv_dtype"] == "float32"
    assert d["blocks_used"] == 2
    assert d["bytes"] == sum(a.nbytes for a in c.arrays())
    assert (d["block_size"], d["num_blocks"]) == (16, 9)

    i8 = gen.PagedKVCache(num_layers=2, num_blocks=9, block_size=16,
                          num_heads=4, head_dim=8, slots=3, max_len=64,
                          kv_dtype="int8")
    assert len(i8.arrays()) == 4 * 2       # + per-head scales per layer
    assert [a.shape for a in i8.arrays()] == (
        [(9, 16, 32)] * 4 + [(9, 16, 4)] * 4)
    assert [str(a.dtype) for a in i8.arrays()] == (
        ["int8"] * 4 + ["float32"] * 4)
    assert i8.nbytes == (2 * 2 * 9 * 16 * 4 * 8 * 1
                         + 2 * 2 * 9 * 16 * 4 * 4)
    assert i8.nbytes < c.nbytes
    assert i8.describe()["kv_dtype"] == "int8"
    assert i8.describe()["bytes"] == sum(a.nbytes for a in i8.arrays())


@pytest.mark.parametrize("kind", ["dense", "paged", "paged-int8"])
def test_cache_update_of_its_own_arrays_is_the_identity(kind):
    """`update(*arrays())` is the inverse of `arrays()`: same objects,
    same order; a call with another count is refused."""
    if kind == "dense":
        c = gen.KVCache(num_layers=3, slots=2, max_len=32, num_heads=4,
                        head_dim=8)
    else:
        c = gen.PagedKVCache(
            num_layers=3, num_blocks=5, block_size=8, num_heads=4,
            head_dim=8, slots=2, max_len=32,
            kv_dtype="int8" if kind == "paged-int8" else None)
    before = c.arrays()
    assert len(before) == (4 if kind == "paged-int8" else 2) * 3
    c.update(*before)
    assert len(c.arrays()) == len(before)
    assert all(a is b for a, b in zip(c.arrays(), before))
    assert c.describe()["bytes"] == sum(a.nbytes for a in before)
    # a cached forward takes the arrays one tuple per layer (K, V, then
    # an int8 pool's two scales); flattening that gives the order back
    layers = kv_cache.group_layers(before, c.num_layers)
    assert len(layers) == 3
    assert all(mine == before[li::3] for li, mine in enumerate(layers))
    flat = kv_cache.flatten_layers(layers)
    assert all(a is b for a, b in zip(flat, before))
    with pytest.raises(ValueError):
        c.update(*before[:2])


# ---------------------------------------------------------------------------
# paged engine drills (PR-17)
# ---------------------------------------------------------------------------


def _run(engine, reqs):
    handles = [engine.submit(gen.GenerationRequest(
        r.prompt_ids, max_new_tokens=r.max_new_tokens,
        sampling=r.sampling, stop_token_ids=r.stop_token_ids))
        for r in reqs]
    engine.run_until_idle()
    return [h.result() for h in handles]


class TestPagedEngine:
    def test_paged_exact_vs_dense_mixed_traffic(self, lm):
        """The acceptance gate: the paged engine is token-for-token the
        PR-15 dense engine under mixed continuous-batching traffic at
        fixed seeds (7 requests over 3 slots: slots free and refill
        mid-flight, blocks migrate between requests)."""
        reqs = mixed_requests(7)
        paged = _run(make_engine(lm), reqs)             # paged default
        dense = _run(make_engine(lm, paged=False), reqs)
        assert paged == dense
        assert any(len(t) > 0 for t in paged)

    @pytest.mark.slow
    def test_chunked_prefill_exact_vs_dense(self, lm):
        reqs = mixed_requests(6)
        chunked = _run(make_engine(lm, prefill_chunk=4), reqs)
        dense = _run(make_engine(lm, paged=False), reqs)
        assert chunked == dense

    @pytest.mark.slow
    def test_prefix_cache_hits_and_exactness(self, lm):
        """Shared-system-prompt traffic: round 2 serves the prefix from
        cache (hits, hit_tokens > 0) and the streams still equal the
        dense engine's."""
        sysp = list(range(1, 34))
        reqs = [gen.GenerationRequest(sysp + [40 + i], max_new_tokens=4,
                                      request_id="p%d" % i)
                for i in range(4)]
        eng = make_engine(lm, prefix_cache=True,
                          prefill_buckets=[8, 16, 40])
        got = _run(eng, reqs)
        dense = _run(make_engine(lm, paged=False,
                                 prefill_buckets=[8, 16, 40]), reqs)
        assert got == dense
        st = eng.stats()["prefix_cache"]
        assert st["hits"] >= 1 and st["hit_tokens"] >= 32
        assert st["entries"] >= 2
        assert eng.occupancy()["active"] == 0
        # the only live pool references left are the registry's
        assert eng.cache.pool.used_blocks == st["entries"]
        # releasing the registry returns every block: no leaks
        eng._prefix.evict(eng.cache.num_blocks - 1)
        assert eng.cache.pool.used_blocks == 0

    @pytest.mark.slow
    def test_speculative_greedy_exact_vs_dense(self, lm):
        """Draft-k speculative decoding: greedy streams equal plain
        decode exactly (verify samples with the SAME per-step PRNG
        states), and the acceptance counters are live."""
        with dygraph.guard():
            np.random.seed(7)
            draft = models.TransformerLM(CFG)
        reqs = mixed_requests(6)
        eng = make_engine(lm, draft_model=draft, draft_len=3)
        got = _run(eng, reqs)
        dense = _run(make_engine(lm, paged=False), reqs)
        assert got == dense
        spec = eng.stats()["speculative"]
        assert spec["draft_len"] == 3
        assert spec["proposed"] > 0
        assert 0.0 <= spec["acceptance_rate"] <= 1.0

    @pytest.mark.slow
    def test_int8_kv_opt_in_smoke(self, lm):
        """kv_dtype='int8' is the documented-tolerance opt-in: streams
        complete at full length (greedy may lawfully differ from f32),
        the pool stores int8 + scales, bytes shrink ~4x."""
        reqs = mixed_requests(5)
        eng = make_engine(lm, kv_dtype="int8")
        got = _run(eng, reqs)
        assert [len(t) for t in got] == \
            [r.max_new_tokens for r in reqs]
        d = eng.cache.describe()
        assert d["kv_dtype"] == "int8"
        f32 = make_engine(lm)
        assert eng.cache.nbytes < f32.cache.nbytes / 2

    def test_midflight_death_returns_every_block(self, lm):
        """The leak drill: an engine killed MID-GENERATION (slots full
        of half-decoded sequences) must hand back every pool block."""
        def hook(step_no):
            if step_no >= 2:
                raise gen.EngineDeadError("drill kill at step 2")

        eng = make_engine(lm, step_hook=hook)
        handles = [eng.submit(r) for r in mixed_requests(3, max_new=8)]
        with pytest.raises(gen.EngineDeadError):
            while eng.step():
                pass
        assert eng.dead
        assert eng.cache.pool.used_blocks == 0
        for h in handles:
            with pytest.raises(Exception):
                h.result(timeout=0.1)

    @pytest.mark.slow
    def test_tiny_pool_preempts_and_completes_everything(self, lm):
        """A pool too small for all slots at once: the engine preempts
        (restart semantics) instead of corrupting or deadlocking;
        every request still completes at full length and the pool
        drains to zero."""
        eng = make_engine(lm, kv_blocks=5, block_size=16)
        reqs = [gen.GenerationRequest(list(range(1, 15)),
                                      max_new_tokens=8,
                                      request_id="tp%d" % i)
                for i in range(3)]
        handles = [eng.submit(r) for r in reqs]
        eng.run_until_idle()
        got = [h.result() for h in handles]
        assert [len(t) for t in got] == [8, 8, 8]
        # 4 usable blocks cannot hold three 22-token sequences at once:
        # the engine MUST have preempted at least one slot
        assert eng.stats()["preempted"] >= 1
        assert eng.cache.pool.used_blocks == 0
        # exactness survives preemption: restarts replay the same
        # per-request key streams
        dense = _run(make_engine(lm, paged=False), reqs)
        assert got == dense

    def test_compile_pin_with_all_features_on(self, lm):
        """The PR-17 compile gate: prefix cache + chunked prefill +
        speculative verify all live, warmed engine, measured traffic
        compiles ZERO executables (PR-4 accumulator)."""
        from paddle_tpu.observability import install_jax_compile_hooks
        from paddle_tpu.observability.metrics import default_registry

        install_jax_compile_hooks()
        ctr = default_registry().counter(
            "xla_compilations_total",
            "XLA backend compilations (jax.monitoring)")
        with dygraph.guard():
            np.random.seed(9)
            draft = models.TransformerLM(CFG)
        eng = make_engine(lm, prefix_cache=True, prefill_chunk=8,
                          draft_model=draft, draft_len=2)
        for r in mixed_requests(6):
            eng.submit(r)
        eng.run_until_idle()
        c0 = ctr.value
        for r in mixed_requests(6):        # same length mix, rides all
            eng.submit(r)                  # warmed executables
        eng.run_until_idle()
        assert ctr.value == c0, (
            "%d executables compiled in the measured run; paged + "
            "prefix + chunk + verify must reuse the warmed set"
            % (ctr.value - c0))
        ex = eng.stats()["executables"]
        assert ex["decode_step"] <= 1 and ex["verify"] == 1

    def test_paged_knobs_require_paged(self, lm):
        with pytest.raises(ValueError):
            make_engine(lm, paged=False, prefix_cache=True)
        with pytest.raises(ValueError):
            make_engine(lm, paged=False, kv_dtype="int8")
        with pytest.raises(ValueError):
            make_engine(lm, paged=False, prefill_chunk=8)
        with pytest.raises(ValueError):
            make_engine(lm, kv_dtype="float16")
        with dygraph.guard():
            np.random.seed(11)
            draft = models.TransformerLM(CFG)
        with pytest.raises(ValueError):
            make_engine(lm, draft_model=draft)     # needs draft_len
        with pytest.raises(ValueError):
            make_engine(lm, paged=False, draft_model=draft,
                        draft_len=2)


def _alone(lm, reqs, **kw):
    """Tokens and log-probabilities of each request served alone in a
    fresh engine: `sequential_oracle` with the log-probabilities."""
    toks, lps = [], []
    for r in reqs:
        eng = make_engine(lm, **kw)
        h = eng.submit(gen.GenerationRequest(
            r.prompt_ids, max_new_tokens=r.max_new_tokens,
            sampling=r.sampling, stop_token_ids=r.stop_token_ids))
        eng.run_until_idle()
        toks.append(h.result())
        lps.append(h.logprobs())
    return toks, lps


class TestStepInFlight:
    """The plain decode step keeps one step in flight: step t+1 is
    dispatched on the device's copy of step t's tokens before those are
    fetched and delivered.  Same tokens, same order; what the host
    learns a step late (a stop token, a preemption) costs a discarded
    row and never a wrong token."""

    @pytest.mark.parametrize("kw", [{}, {"paged": False},
                                    {"kv_dtype": "int8"}],
                             ids=["paged", "dense", "int8"])
    def test_streams_and_logprobs_equal_the_oracle(self, lm, kw):
        reqs = mixed_requests(7)
        eng = make_engine(lm, logprobs=True, **kw)
        handles = [eng.submit(r) for r in reqs]
        in_flight = 0
        while eng.step():
            in_flight += eng._in_flight is not None
        assert in_flight > 10 and eng._in_flight is None
        toks, lps = _alone(lm, reqs, logprobs=True, **kw)
        assert [h.result() for h in handles] == toks
        for h, want in zip(handles, lps):
            np.testing.assert_allclose(h.logprobs(), want, rtol=0,
                                       atol=1e-6)
        st = eng.stats()
        assert st["decode_overlapped"] >= st["decode_steps"] - 1
        # every request ends by length: known at dispatch, no row wasted
        assert st["decode_rows_discarded"] == 0
        assert st["executables"]["decode_step"] == 1

    def test_a_stop_token_costs_one_row_and_the_slot_is_clean(self, lm):
        """The stop token is known only at the fetch, so its slot rides
        one more step; that row is dropped, and the request admitted
        into the freed slot and blocks is exact."""
        from paddle_tpu.observability.metrics import MetricsRegistry

        long = gen.GenerationRequest([9, 8, 7, 6], max_new_tokens=14,
                                     sampling=_sampled(5), request_id="c")
        probe = make_engine(lm).generate(
            [[5, 7, 9]], max_new_tokens=8, sampling=_sampled(4))[0]
        # the first token from the third on that the stream has not held
        k = next(i for i in range(2, 8) if probe[i] not in probe[:i])
        stopper = gen.GenerationRequest([5, 7, 9], max_new_tokens=8,
                                        sampling=_sampled(4),
                                        stop_token_ids=(probe[k],),
                                        request_id="a")
        after = gen.GenerationRequest([11, 12, 13, 14, 15],
                                      max_new_tokens=7,
                                      sampling=_sampled(6), request_id="b")
        reqs = [long, stopper, after]
        reg = MetricsRegistry()
        eng = make_engine(lm, slots=2, metrics_registry=reg)
        handles = [eng.submit(r) for r in reqs]
        eng.run_until_idle()
        assert handles[1].result() == probe[:k + 1]
        assert handles[1].finish_reason == "stop_token"
        assert [h.result() for h in handles] == gen.sequential_oracle(
            lambda: make_engine(lm, slots=2), reqs)
        assert eng.stats()["decode_rows_discarded"] == 1
        assert eng.cache.pool.used_blocks == 0
        text = reg.prometheus_text()
        assert "generation_decode_rows_discarded_total" in text
        assert "generation_decode_overlapped_total" in text

    def test_a_preempted_slots_row_in_flight_is_dropped(self, lm):
        """Preempted with a row in flight and admitted again into the
        SAME slot before that row is fetched: the restarted stream
        holds no token of its former life (rows are matched by the
        slot's state object, not by its index)."""
        req = gen.GenerationRequest([3, 1, 4, 1, 5], max_new_tokens=9,
                                    sampling=_sampled(8))
        eng = make_engine(lm, slots=1)
        h = eng.submit(req)
        for _ in range(3):
            assert eng.step()
        (slot, before), = eng._in_flight[1]
        with eng._lock:
            eng._preempt_slot(slot, "test")
        assert eng.step()               # admits it again, same slot
        assert eng._slot_state[slot] is not before
        assert eng._slot_state[slot].generated == 1    # its token 0 only
        eng.run_until_idle()
        events = list(h.events(timeout=1))
        last = max(i for i, e in enumerate(events) if e[0] == "restart")
        assert [e[1] for e in events[last + 1:-1]] == list(range(9))
        assert h.result() == gen.sequential_oracle(
            lambda: make_engine(lm, slots=1), [req])[0]
        assert eng.stats()["decode_rows_discarded"] == 1
        assert eng.stats()["preempted"] == 1

    def test_death_with_a_step_in_flight_requeues_everyone_once(self, lm):
        """The death drill while a step is un-fetched: the in-flight
        step is abandoned, and every handle (decoding, waiting for its
        last row, queued) is handed to `on_death` exactly once."""
        seen = {}

        def hook(step_no):
            if step_no >= 2:
                seen["in_flight"] = eng._in_flight is not None
                seen["waiting"] = [
                    st is not None and not eng._active[s]
                    for s, st in enumerate(eng._slot_state)]
                raise gen.EngineDeadError("drill")

        eng = make_engine(lm, slots=3, step_hook=hook)
        requeued = []
        eng.on_death = lambda engine, affected: requeued.extend(affected)
        # the second request's last row (its third token) is in flight
        # when the engine dies
        handles = [eng.submit(gen.GenerationRequest(
            [2 + i, 3, 4], max_new_tokens=n))
            for i, n in enumerate([8, 3, 8, 8])]
        with pytest.raises(gen.EngineDeadError):
            while eng.step():
                pass
        assert seen["in_flight"] and seen["waiting"] == [False, True, False]
        assert eng.dead and eng._in_flight is None
        assert sorted(map(id, requeued)) == sorted(map(id, handles))
        assert not any(h.done for h in handles)
        assert eng.cache.pool.used_blocks == 0

    def test_step_by_hand_delivers_the_last_step(self, lm):
        """`step()` is True while anything is queued, live or
        un-fetched: the iteration that only delivers counts."""
        eng = make_engine(lm)
        h = eng.submit(gen.GenerationRequest([5, 6, 7], max_new_tokens=3))
        assert eng.step()               # prefill (token 0), dispatch
        assert len(h._tokens) == 1 and eng._in_flight is not None
        assert eng.step()               # dispatch the last row, deliver
        assert len(h._tokens) == 2 and not eng._active.any()
        assert eng._in_flight is not None and not h.done
        assert eng.step()               # nothing to dispatch: deliver
        assert h.done and len(h.result()) == 3
        assert eng._in_flight is None and not eng.step()
        more = [eng.submit(r) for r in mixed_requests(4)]
        eng.run_until_idle()
        assert all(m.done for m in more) and eng._in_flight is None
        assert not eng.step()

    def test_a_steady_batch_overlaps_every_step_but_the_first(self, lm):
        """Four long requests, all admitted by the first iteration:
        every later step is dispatched while the one before is
        un-fetched, in one executable, and `generation_itl_ms` has one
        observation a step."""
        eng = make_engine(lm, slots=4)
        handles = [eng.submit(gen.GenerationRequest(
            [7 + i, 8, 9], max_new_tokens=40,
            sampling=None if i % 2 else _sampled(20 + i)))
            for i in range(4)]
        eng.run_until_idle()
        assert [len(h.result()) for h in handles] == [40] * 4
        st = eng.stats()
        assert st["decode_steps"] == 39
        assert st["decode_overlapped"] / st["decode_steps"] > 0.9
        assert st["decode_rows_discarded"] == 0
        assert st["executables"]["decode_step"] == 1
        assert eng._m_itl.summary()["count"] == st["decode_steps"]

    def test_itl_leaves_out_the_gap_that_held_a_prefill(self, lm):
        """`generation_itl_ms` is what a live stream waits while no
        prompt goes in: the one gap with the late request's prefill in
        it is `generation_prefill_ms`'s."""
        eng = make_engine(lm, slots=2)
        eng.submit(gen.GenerationRequest([5, 6, 7], max_new_tokens=12))
        for _ in range(4):
            eng.step()
        eng.submit(gen.GenerationRequest([8, 9], max_new_tokens=4))
        eng.run_until_idle()
        st = eng.stats()
        assert st["decode_overlapped"] == st["decode_steps"] - 1
        assert eng._m_itl.summary()["count"] == st["decode_steps"] - 1
        assert eng._m_prefill_ms.summary()["count"] == 2


def test_tune_generation_block_and_draft_axes():
    from paddle_tpu.tune.space import generation_config_candidates

    cands = generation_config_candidates(
        slot_counts=(4,), max_len=128, block_sizes=(16, 32),
        draft_lens=(0, 4))
    assert [c.label for c in cands] == [
        "slots4_bs16_k0", "slots4_bs16_k4",
        "slots4_bs32_k0", "slots4_bs32_k4"]
    assert cands[0].params["block_size"] == 16
    assert cands[1].params["draft_len"] == 4
    # legacy call shape unchanged: no paged keys, no suffixes
    legacy = generation_config_candidates(slot_counts=(4,), max_len=128)
    assert legacy[0].label == "slots4"
    assert "block_size" not in legacy[0].params


# ---------------------------------------------------------------------------
# the sampler inside the step functions: no sort, and one argmax for a
# step whose live rows are all greedy
# ---------------------------------------------------------------------------


def _sampled(seed, **kw):
    kw.setdefault("temperature", 0.8)
    kw.setdefault("top_k", 40)
    kw.setdefault("top_p", 0.95)
    return gen.SamplingParams(seed=seed, **kw)


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk", "verify",
                                     "the sort sampler"])
def test_generation_programs_sort_nothing(lm, program):
    """Lowered at the tiny size, no generation program holds a `sort`
    (or a `top_k`): the policies are operands, so this is the program
    for the cell's policies and for any others.  The reference sampler's
    text, lowered the same way, is what the search would find."""
    import jax

    if program == "the sort sampler":
        n, v = 3, CFG.vocab_size
        text = jax.jit(sort_sampler).lower(
            np.zeros((n, v), np.float32), np.zeros((n, 2), np.uint32),
            np.zeros(n, np.int32), np.ones(n, np.float32),
            np.ones(n, np.int32), np.ones(n, np.float32)).as_text()
        assert "stablehlo.sort" in text
        return
    with dygraph.guard():
        np.random.seed(1)
        draft = models.TransformerLM(CFG)
    eng = make_engine(lm, prefill_chunk=4, draft_model=draft, draft_len=2)
    arrays = eng.cache.arrays()
    table = eng.cache.table_row(0)[None].astype(np.int32)
    policy = (np.float32(0.8), np.int32(40), np.float32(0.95))
    key = np.zeros(2, np.uint32)
    fn, operands = {
        "decode": (eng._decode_step_fn, eng._decode_operands()),
        "prefill": (eng._prefill_fns[8], (
            eng._params, *arrays, np.zeros((1, 8), np.int32), np.int32(8),
            table, key, *policy)),
        "chunk": (jax.jit(eng._make_chunk_fn(4)), (     # jitted lazily
            eng._params, *arrays, np.zeros((1, 4), np.int32), np.int32(0),
            table, np.int32(3), key, *policy)),
        "verify": (eng._verify_fn, (
            eng._params, *arrays, eng._lengths,
            np.zeros((eng.slots, 3), np.int32), eng._keys, eng._steps,
            eng._temp, eng._top_k, eng._top_p, eng._decode_tables())),
    }[program]
    text = fn.lower(*operands).as_text()
    assert "stablehlo.sort" not in text and "top_k" not in text
    assert "stablehlo.while" in text and "stablehlo.case" in text


@pytest.mark.parametrize("how", ["finish", "preempt", "fail", "death"])
def test_a_parked_slot_reads_as_greedy(lm, how):
    """However a sampled request leaves its slot, the slot's policy goes
    back to greedy (0, 0, 1): a free slot must not make a step of greedy
    requests take the sampling branch."""
    eng = make_engine(lm, slots=2)
    h = eng.submit(gen.GenerationRequest(
        [5, 6, 7], max_new_tokens=6, sampling=_sampled(3)))
    eng.step()
    slot = int(np.nonzero(eng._active)[0][0])
    assert eng._temp[slot] == np.float32(0.8) and eng._top_k[slot] == 40
    if how == "finish":
        eng.run_until_idle()
        assert len(h.result()) == 6
    elif how == "preempt":
        with eng._lock:
            eng._preempt_slot(slot, "test")
    elif how == "fail":
        with eng._lock:
            eng._fail_slot(slot, "test")
    else:
        eng._die("test")
    assert not eng._active.any()
    np.testing.assert_array_equal(eng._temp, np.zeros(2, np.float32))
    np.testing.assert_array_equal(eng._top_k, np.zeros(2, np.int32))
    np.testing.assert_array_equal(eng._top_p, np.ones(2, np.float32))
    if how == "preempt":        # restarted from the queue, same stream
        eng.run_until_idle()
        fresh = make_engine(lm, slots=2).generate(
            [[5, 6, 7]], max_new_tokens=6, sampling=_sampled(3))
        assert h.result() == fresh[0]


@pytest.mark.parametrize("mix,share", [("greedy", 0.0), ("sampled", 1.0),
                                       ("both", None)])
def test_sampling_step_share_counts_steps_with_a_sampling_row(lm, mix,
                                                              share):
    """`generation_sampling_step_share`: 1 for a decode step with a live
    sampling row, 0 for an all-greedy one; its mean in `stats()`, the
    family on /metrics.  In the mixed run the sampled request ends
    first, so the steps after it are greedy again."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    eng = make_engine(lm, metrics_registry=reg)
    assert eng.stats()["sampling_step_share"] is None
    policies = {"greedy": [None, None], "sampled": [_sampled(1), _sampled(2)],
                "both": [None, _sampled(1)]}[mix]
    news = [9, 4] if mix == "both" else [5, 5]
    handles = [eng.submit(gen.GenerationRequest(
        [3 + i, 4, 5], max_new_tokens=n, sampling=sp))
        for i, (sp, n) in enumerate(zip(policies, news))]
    eng.run_until_idle()
    assert [len(h.result()) for h in handles] == news
    got = eng.stats()["sampling_step_share"]
    summary = eng._m_sampling.summary()
    assert summary["count"] == eng.stats()["decode_steps"]
    if share is None:
        # the prefill gave token 0: 3 more steps with the sampled
        # request live, then 5 with the greedy one alone
        assert got == pytest.approx(3 / 8)
    else:
        assert got == share
    text = reg.prometheus_text()
    assert "generation_sampling_step_share_count" in text
    assert "generation_sampling_step_share_sum" in text


def test_a_greedy_stream_does_not_depend_on_its_neighbours_policy(lm):
    """A greedy request decodes the same tokens whether the other slots
    are greedy (every step the argmax branch), sampling (every step the
    selection branch) or empty."""
    prompt, new = [9, 8, 7, 6], 8
    alone = make_engine(lm).generate([prompt], max_new_tokens=new)[0]
    for neighbours in ([None, None], [_sampled(4), _sampled(5)]):
        eng = make_engine(lm)
        out = eng.generate(
            [prompt, [1, 2, 3], [4, 5]], max_new_tokens=new,
            sampling=[None] + neighbours)
        assert out[0] == alone
        assert eng.stats()["sampling_step_share"] == float(
            neighbours[0] is not None)


# ---------------------------------------------------------------------------
# the dispatch split into its parts, and the instruments that time them
# ---------------------------------------------------------------------------

DISPATCH_PARTS = ["generation.dispatch_call", "generation.dispatch_operands"]


@pytest.fixture
def ring():
    from paddle_tpu.observability import trace as T

    tr = T.enable_tracing()
    tr.clear()
    yield tr
    T.disable_tracing()
    tr.clear()


def recording_fresh(monkeypatch, eng, method, fresh):
    """Wrap ``eng.<method>`` (a dispatch) to note, before each call,
    whether the host had changed rows since the dispatch before."""
    seen, real = [], getattr(eng, method)

    def dispatch(*a, **kw):
        seen.append(bool(fresh().any()))
        return real(*a, **kw)

    monkeypatch.setattr(eng, method, dispatch)
    return seen


def dispatch_children(events):
    """For each `generation.decode_dispatch` span of the ring, in order,
    the names of the `generation.dispatch_*` spans nested in it on its
    thread."""
    xs = [e for e in events if e.get("ph") == "X"]
    out = []
    for d in sorted((e for e in xs
                     if e["name"] == "generation.decode_dispatch"),
                    key=lambda e: e["ts"]):
        out.append(sorted(
            e["name"] for e in xs
            if e["name"].startswith("generation.dispatch_")
            and e["tid"] == d["tid"] and d["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= d["ts"] + d["dur"]))
    return out


def assert_dispatch_split(eng, reg, events, fresh):
    """Every dispatch holds its operands and its call, and a merge
    exactly where the host had changed rows; the two dispatch
    histograms count one observation a dispatch, and the step counter
    is `decode_steps`."""
    children = dispatch_children(events)
    assert len(children) == len(fresh) == eng.stats()["decode_steps"]
    assert children == [sorted(DISPATCH_PARTS + ["generation.dispatch_merge"]
                               if f else DISPATCH_PARTS) for f in fresh]
    assert any(fresh) and not all(fresh)
    for family in ("generation_dispatch_ms", "generation_dispatch_call_ms"):
        assert reg.get(family).labels(eng._engine).summary()["count"] == \
            len(fresh)
    steps = reg.get("generation_decode_steps_total").labels(eng._engine)
    assert steps.value == eng.stats()["decode_steps"]


def test_every_dispatch_holds_its_parts_and_a_merge_where_rows_changed(
        lm, ring, monkeypatch):
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    eng = make_engine(lm, metrics_registry=reg)
    fresh = recording_fresh(monkeypatch, eng, "_dispatch_decode",
                            lambda: eng._changed_rows()[1])
    handles = [eng.submit(r) for r in mixed_requests(7)]
    eng.run_until_idle()
    assert all(h.done for h in handles)
    assert_dispatch_split(eng, reg, ring.events(), fresh)


class _TickClock:
    """``time`` whose `perf_counter` advances one tick a read, so that a
    duration is the number of reads it spans."""

    def __init__(self):
        self.n = 0

    def perf_counter(self):
        self.n += 1
        return float(self.n)

    def __getattr__(self, name):
        import time

        return getattr(time, name)


def _sched_host_in_ticks(lm, monkeypatch, parts):
    from paddle_tpu.generation import engine as engine_mod
    from paddle_tpu.observability.metrics import MetricsRegistry

    monkeypatch.setattr(engine_mod, "time", _TickClock())
    reg = MetricsRegistry()
    eng = make_engine(lm, metrics_registry=reg)
    if not parts:       # the call as it was before the dispatch's parts
        eng._call_decode_step = lambda: eng._decode_step_fn(
            *eng._decode_operands())
    try:
        for r in mixed_requests(5):
            eng.submit(r)
        eng.run_until_idle()
    finally:
        monkeypatch.undo()
    s = reg.get("generation_sched_host_ms").labels(eng._engine).summary()
    return s["count"], s["sum"]


def test_the_dispatch_parts_leave_sched_host_as_it_was(lm, monkeypatch):
    """`generation_sched_host_ms` is a step less its device calls: the
    dispatch's parts lie inside `generation.decode_dispatch` and add
    nothing to its device share.  On a clock that ticks once a read, the
    engine with the parts and the same engine calling its step as
    before observe the same."""
    with_parts = _sched_host_in_ticks(lm, monkeypatch, True)
    without = _sched_host_in_ticks(lm, monkeypatch, False)
    assert with_parts == without
    assert with_parts[0] > 5 and with_parts[1] > 0


def test_a_collection_is_one_span_and_one_observation(lm, ring):
    """A running engine installs one `gc.callbacks` hook for the process:
    a forced full collection is one `generation.gc` span (generation 2,
    with what it collected, on the thread that collected) and one
    `generation_gc_pause_ms` observation in the engine's own registry,
    and the hook is gone once the engine stops."""
    import gc
    import threading

    from paddle_tpu.generation import engine as engine_mod
    from paddle_tpu.observability.metrics import MetricsRegistry

    hooks = len(gc.callbacks)
    reg = MetricsRegistry()
    eng = make_engine(lm, metrics_registry=reg).start()
    gc.disable()        # no other collection until the count is read
    try:
        engine_mod._COLLECTIONS.drain()
        hist = reg.get("generation_gc_pause_ms")
        before = hist.summary()["count"]
        ring.clear()
        gc.collect()
        eng.stop()      # reports what the loop has not yet
        spans = [e for e in ring.events()
                 if e.get("name") == "generation.gc"]
        after = hist.summary()
    finally:
        gc.enable()
        eng.stop()
    assert len(spans) == 1
    assert spans[0]["args"]["generation"] == 2
    assert spans[0]["args"]["collected"] >= 0
    assert spans[0]["tid"] == threading.get_ident()
    assert after["count"] == before + 1
    assert len(gc.callbacks) == hooks


class _CollectingList(list):
    """A list whose copy runs a full collection first: a collection at
    an allocation made under the lock that guards the list."""

    def __iter__(self):
        import gc

        gc.collect()
        return super().__iter__()


def test_a_collection_under_the_tracers_lock_does_not_block_its_thread(
        lm, ring):
    """`Tracer.events()` copies its thread names under the tracer's own
    lock; a collection there, on a thread the tracer has not seen (an
    HTTP handler serving `/trace`), runs the hook on that thread.  The
    hook takes no lock, so the thread goes on, and its collection is
    reported all the same."""
    import threading

    names = ring._meta_events
    eng = make_engine(lm).start()
    try:
        ring._meta_events = _CollectingList(names)
        done = []
        reader = threading.Thread(
            target=lambda: done.append(len(ring.events())), daemon=True)
        reader.start()
        reader.join(timeout=30)
        assert not reader.is_alive() and done
        h = eng.submit(gen.GenerationRequest([1, 2, 3], max_new_tokens=2))
        assert len(h.result(timeout=60)) == 2
    finally:
        collecting, ring._meta_events = ring._meta_events, names
        names.extend(collecting[len(names):])   # threads named meanwhile
        eng.stop()
    assert any(e.get("name") == "generation.gc" and e["tid"] == reader.ident
               for e in ring.events())


def test_the_collection_hook_is_shared_and_counted(lm):
    """Two engines running share the one hook; it stays until the last
    one stops, and an engine stopped twice releases it once."""
    import gc

    from paddle_tpu.generation import engine as engine_mod

    hook = engine_mod._COLLECTIONS._callback
    hooks = len(gc.callbacks)
    a, b = make_engine(lm).start(), make_engine(lm).start()
    try:
        assert gc.callbacks.count(hook) == 1
        a.stop()
        a.stop()
        assert gc.callbacks.count(hook) == 1
    finally:
        a.stop()
        b.stop()
    assert len(gc.callbacks) == hooks


# ---------------------------------------------------------------------------
# the step's per-slot state on the device, and the merge of the host's rows
# ---------------------------------------------------------------------------

def _scenario_requests():
    """Seven requests over three slots: greedy ones and sampled ones
    under different temperatures, top-k and top-p, two of them sharing
    an eight-token prefix (a prefix-cache hit at a block of four)."""
    rng = np.random.RandomState(7)
    shared = [int(t) for t in rng.randint(0, 97, 8)]
    policies = [None, _sampled(11), _sampled(12, temperature=1.1, top_k=5,
                                             top_p=0.8),
                None, _sampled(14, temperature=0.6, top_k=0, top_p=0.9),
                _sampled(15, temperature=0.9, top_k=20, top_p=1.0), None]
    lengths = [(5, 12), (13, 9), (3, 14), (shared, 7),
               (shared + [3, 4, 5], 10), (9, 6), (2, 11)]
    reqs = []
    for i, ((prompt, new), sp) in enumerate(zip(lengths, policies)):
        if not isinstance(prompt, list):
            prompt = [int(t) for t in rng.randint(0, 97, prompt)]
        reqs.append(gen.GenerationRequest(prompt, max_new_tokens=new,
                                          sampling=sp, request_id="s%d" % i))
    return reqs


# request i is submitted at scheduler iteration ARRIVALS[i]; at iteration
# 7 the slot of request 1 is preempted (it is admitted again later)
ARRIVALS = [0, 0, 2, 3, 9, 12, 14]
PREEMPT = (7, 1)


def _serve_scenario(eng, reqs):
    handles, it = {}, 0
    while True:
        for i, at in enumerate(ARRIVALS):
            if at == it:
                handles[i] = eng.submit(reqs[i])
        if it == PREEMPT[0]:
            slot = next(s for s, st in enumerate(eng._slot_state)
                        if st is not None and st.request is reqs[PREEMPT[1]])
            with eng._lock:
                eng._preempt_slot(slot, "test")
        busy = eng.step()
        it += 1
        if not busy and len(handles) == len(reqs):
            return [handles[i] for i in range(len(reqs))]


PARENT_STREAMS = {
    "autoregressive": (
        [   # tokens
            [42, 42, 10, 10, 10, 10, 10, 10, 10, 10, 10, 10],
            [120, 89, 85, 43, 7, 16, 6, 28, 112],
            [49, 68, 44, 64, 10, 10, 104, 67, 14, 35, 69, 69, 110, 67],
            [57, 57, 57, 57, 57, 57, 57],
            [44, 4, 93, 75, 123, 26, 62, 62, 26, 125],
            [86, 86, 80, 10, 12, 12],
            [38, 38, 38, 38, 38, 10, 10, 10, 10, 10, 10],
        ], [   # log-probabilities
            [-4.5276718, -4.4971628, -4.4944148, -4.3783927, -4.4178009,
             -4.5074482, -4.3610692, -4.4249015, -4.3846679, -4.3638353,
             -4.5532761, -4.4478283],
            [-4.7343082, -4.8111601, -4.7839551, -4.8062482, -4.7032127,
             -4.6577692, -4.7562304, -4.7583184, -4.5633693],
            [-4.6416502, -4.6102209, -4.5268469, -4.5646496, -4.6007233,
             -4.3853588, -4.5826159, -4.6276913, -4.5807705, -4.6054130,
             -4.6624274, -4.4650664, -4.6557264, -4.6334324],
            [-4.3225608, -4.2726202, -4.3933945, -4.3364573, -4.4375873,
             -4.4028692, -4.4032292],
            [-4.7372479, -4.8661561, -4.9344234, -4.7807388, -4.9347587,
             -4.7228637, -4.6326270, -4.4710913, -4.8167915, -4.7980475],
            [-4.6623163, -4.4313688, -4.6170034, -4.7042589, -4.5299759,
             -4.3409967],
            [-4.3986249, -4.3981905, -4.4232688, -4.4583941, -4.4956684,
             -4.5640116, -4.4076843, -4.4592032, -4.5384283, -4.3965750,
             -4.4483256],
        ]),
    "block": (
        [   # tokens
            [17, 17, 24, 17, 17, 111, 24, 24, 24, 24, 111, 24],
            [34, 89, 22, 42, 10, 34, 29, 114, 101],
            [24, 90, 97, 24, 42, 17, 17, 24, 17, 42, 24, 42, 111, 17],
            [17, 17, 17, 17, 17, 17, 17],
            [87, 4, 93, 75, 123, 112, 31, 62, 26, 125],
            [86, 86, 80, 10, 90, 82],
            [99, 99, 97, 97, 56, 56, 5, 5, 34, 34, 56],
        ], [   # log-probabilities
            [-4.4705906, -4.5009909, -4.3736844, -4.4239860, -4.4478192,
             -4.4019060, -4.3864088, -4.4369192, -4.4263997, -4.4150949,
             -4.4156699, -4.4258318],
            [-4.5245271, -4.7547975, -4.7453680, -4.6775632, -4.6831474,
             -4.4694047, -4.7694430, -4.7240887, -4.7052760],
            [-4.5093379, -4.4585547, -4.4971766, -4.5014830, -4.4253063,
             -4.4959230, -4.5202208, -4.4988289, -4.4585114, -4.5341663,
             -4.5050211, -4.5418019, -4.5156856, -4.4537549],
            [-4.3335061, -4.3275766, -4.2758737, -4.3151569, -4.3024654,
             -4.3116727, -4.3044739],
            [-4.7681823, -4.9593668, -4.6908779, -4.6174421, -5.0099521,
             -4.8067503, -4.9715466, -4.9865417, -4.8987513, -4.8453140],
            [-4.4846835, -4.4230199, -4.6406989, -4.6029921, -4.4701753,
             -4.6904001],
            [-4.4143195, -4.4333215, -4.4820342, -4.4791117, -4.4707422,
             -4.4700317, -4.4672055, -4.4733205, -4.4819894, -4.4548373,
             -4.5490141],
        ]),
}


def _scenario_engine(kind):
    from paddle_tpu.models.moe_decoder import MoEDecoderConfig, MoEDecoderLM

    reqs = _scenario_requests()
    if kind == "block":
        lm = MoEDecoderLM.seeded(MoEDecoderConfig.tiny(), 3)
        return gen.GenerationEngine(
            lm, slots=3, max_len=64, logprobs=True, denoising_steps=2,
            prefill_chunk=8), [gen.GenerationRequest(
                [t % 120 for t in r.prompt_ids],
                max_new_tokens=r.max_new_tokens, sampling=r.sampling,
                request_id=r.request_id) for r in reqs]
    with dygraph.guard():
        np.random.seed(0)
        lm = models.TransformerLM(CFG)
    kw = ({"paged": False} if kind == "dense" else
          {"block_size": 4, "prefix_cache": True, "prefill_chunk": 4})
    return make_engine(lm, logprobs=True, **kw), reqs


@pytest.mark.parametrize("kind", ["paged", "dense", "block"])
def test_streams_equal_the_parents_through_every_change_of_a_row(kind,
                                                                 monkeypatch):
    """Admissions while others decode, prompts going in by chunks beside
    live decoders (paged: four tokens a chunk; block diffusion: every
    prompt), grows (blocks of four), a prefix-cache hit, parks and frees
    by length, a preemption and its re-admission, blocks opened and
    committed, under mixed policies: every row the host changes reaches
    the device's per-slot state, so each stream is the one the engine
    served before that state stayed on the device (`PARENT_STREAMS`,
    taken on the parent tree; paged and dense serve the same tokens).
    `generation_state_merges_total` counts one a dispatch that had a
    changed row and none for one that had not."""
    eng, reqs = _scenario_engine(kind)
    changed = recording_fresh(
        monkeypatch, eng,
        "_dispatch_block" if kind == "block" else "_dispatch_decode",
        lambda: eng._changed_rows()[1])
    handles = _serve_scenario(eng, reqs)
    tokens, logprobs = PARENT_STREAMS[
        "block" if kind == "block" else "autoregressive"]
    assert [h.result() for h in handles] == tokens
    for h, want in zip(handles, logprobs):
        np.testing.assert_allclose(h.logprobs(), want, rtol=0, atol=1e-6)
    st = eng.stats()
    assert st["preempted"] == 1
    if kind == "paged":
        assert st["prefix_cache"]["hits"] == 2
    assert len(changed) == st["decode_steps"]
    assert eng._m_merges.value == sum(changed) < len(changed)
    assert eng._jit_cache_size(eng._merge_state_fn) == 1
    assert st["executables"]["decode_step"] == 1


@pytest.mark.parametrize("kw", [{}, {"paged": False}],
                         ids=["paged", "dense"])
def test_a_step_where_no_row_changed_puts_nothing_on_the_device(lm, kw):
    """Three live decoders: once they are admitted, a scheduler
    iteration whose rows the host has not changed (no admission, park
    or free since the dispatch before, and no grow before its own
    dispatch) dispatches and delivers with every host-to-device
    transfer refused."""
    import jax

    eng = make_engine(lm, **kw)
    for i in range(3):
        eng.submit(gen.GenerationRequest(
            [3 + i, 4, 5], max_new_tokens=40,
            sampling=None if i % 2 else _sampled(30 + i)))
    eng.step()

    def grows():
        return eng.paged and any(
            eng.cache.blocks_for(int(eng._lengths[s]) + 1)
            > len(eng._slot_blocks[s]) for s in np.nonzero(eng._active)[0])

    guarded = 0
    while eng._active.any():
        if eng._changed_rows()[1].any() or grows():
            eng.step()
            continue
        with jax.transfer_guard_host_to_device("disallow"):
            eng.step()
        guarded += 1
    eng.run_until_idle()
    # 39 steps a request, a merge at an admission, a grow (blocks of 16)
    # or a park
    assert guarded >= 30
    assert eng._m_merges.value == eng.stats()["decode_steps"] - guarded


@pytest.mark.parametrize("kw", [{}, {"paged": False},
                                {"kv_dtype": "int8"}],
                         ids=["paged", "dense", "int8"])
def test_the_dispatched_step_adds_only_the_advance_of_lengths_and_steps(
        lm, kw):
    """`generation_decode` as the dispatch calls it is its body (the
    program whose lowered text `test_moe_decoder` pins) with the
    operands' lengths and steps after its outputs, each advanced by one
    in the rows the step writes and nowhere else."""
    import jax

    from paddle_tpu.generation.engine import _named

    eng = make_engine(lm, slots=4, **kw)
    for i in range(2):
        eng.submit(gen.GenerationRequest([3 + i, 4, 5, 6][:2 + i],
                                         max_new_tokens=9))
    eng.step()
    eng.step()
    with eng._lock:
        eng._merge_state()
        ops = eng._decode_operands()
        body = jax.jit(_named(eng._make_decode_fn(), "generation_decode"))
        want = body(*ops)
        got = eng._decode_step_fn(*ops)
    assert len(got) == len(want) + 2
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    live = eng._active.copy()
    assert live.sum() == 2
    np.testing.assert_array_equal(np.asarray(got[-2]), eng._lengths + live)
    np.testing.assert_array_equal(np.asarray(got[-1]), eng._steps + live)
