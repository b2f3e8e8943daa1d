"""`models.MoEDecoderLM` (grouped K/V heads, q/k norm, rotary positions,
gated experts, block mask) and `GenerationEngine`'s block-diffusion step,
held to the plain reference `chipbench/references/sdar_moe.py` at a tiny
size: LOGITS and log-probabilities, never tokens.

Tolerance 1e-5 throughout, and why: both sides compute in float32 at
``highest`` precision here (`tests/conftest.py`), so they differ only by
the order in which 16 to 128 products are summed (the walk's online
softmax, the experts as one matmul against a loop): 5e-7 measured, on
log-probabilities near -4.8 and logits under 1."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.references import sdar_moe as ref  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.fluid.dygraph import to_variable  # noqa: E402
from paddle_tpu.generation import (  # noqa: E402
    GenerationEngine,
    GenerationRequest,
    SamplingParams,
)
from paddle_tpu.generation.engine import (  # noqa: E402
    REMASKING_RULES,
    _TRACE_LOCK,
    choose_reveals,
)
from paddle_tpu.models.moe_decoder import (  # noqa: E402
    GatedExperts,
    MoEDecoderConfig,
    MoEDecoderLM,
)

ATOL = 1e-5
MASK = 127
CONFIG = dict(num_attention_heads=8, num_key_value_heads=2, head_dim=16,
              num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
              rms_norm_eps=1e-6, rope_theta=1e6, num_hidden_layers=2)
# prompt lengths 0-3 mod 4 (3: shorter than a block, nothing to prefill),
# outputs that are no multiple of 4, one prompt holding the mask id
REQUESTS = ((9, 7), (6, 10), (4, 5), (3, 3), (11, 6), (8, 8))


@pytest.fixture(scope="module")
def lm():
    return MoEDecoderLM.seeded(MoEDecoderConfig.tiny(), 3)


@pytest.fixture(scope="module")
def params(lm):
    return {k: v.data for k, v in lm.state_dict().items()}


def prompt_of(n):
    ids = [int(t) for t in np.random.RandomState(n).randint(0, 127, n)]
    if n == 6:
        ids[2] = MASK
    return ids


def serve(lm, rule, steps, slots=2, threshold=0.0085, sampled=(4,), **kw):
    eng = GenerationEngine(lm, slots=slots, max_len=64, logprobs=True,
                           denoising_steps=steps,
                           remasking=rule, confidence_threshold=threshold,
                           **kw)
    handles = []
    for n_p, n_o in REQUESTS:
        sp = SamplingParams(temperature=0.7 if n_p in sampled else 0.0,
                            top_k=5, top_p=0.9, seed=n_p)
        handles.append(eng.submit(GenerationRequest(
            prompt_of(n_p), max_new_tokens=n_o, sampling=sp)))
    eng.run_until_idle()
    return eng, handles


@pytest.mark.parametrize("granule,n", [(4, 23), (4, 16), (1, 11), (2, 9)])
def test_full_forward_under_the_block_mask_equals_the_reference(
        granule, n, params):
    cfg = MoEDecoderConfig.tiny(block_length=granule)
    lm = MoEDecoderLM.seeded(cfg, 3)
    ids = np.random.RandomState(n).randint(0, 128, n)
    logits = lm(to_variable(ids[None]), to_variable(np.arange(n)[None])).data
    want = ref.forward_logits(params, ids, CONFIG, granule)
    assert float(jnp.max(jnp.abs(
        jax.nn.log_softmax(logits[0]) - want))) < ATOL


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rule", REMASKING_RULES)
def test_served_logprobs_equal_the_reveal_state_scorer(rule, steps, lm,
                                                       params):
    """Prefill, then block steps through the engine's paged cache, two
    slots shared by six requests (so slots sit at different passes of
    different blocks in one call), against the reference's scorer at the
    pass the ENGINE recorded for each token."""
    eng, handles = serve(lm, rule, steps)
    for (n_p, n_o), h in zip(REQUESTS, handles):
        tokens, lps = h.result(), h.logprobs()
        assert len(tokens) == n_o == len(lps) == len(h.reveal_passes)
        if rule == "sequential":
            assert h.reveal_passes == ref.sequential_passes(n_p, n_o, 4,
                                                            steps)
        want = ref.reveal_logprobs(params, prompt_of(n_p), tokens,
                                   h.reveal_passes, CONFIG, 4, MASK,
                                   pad_rows=8)
        assert np.max(np.abs(np.asarray(lps) - want)) < ATOL
    assert eng.stats()["decode_executables"] == 1


def test_the_dynamic_rule_reveals_more_than_the_static_number(lm):
    static = serve(lm, "low_confidence_static", 4)[0].stats()
    dynamic = serve(lm, "low_confidence_dynamic", 4)[0].stats()
    assert (dynamic["block_diffusion"]["passes"]
            < static["block_diffusion"]["passes"])


@pytest.mark.parametrize("rule", REMASKING_RULES)
def test_one_forward_scorer_equals_one_forward_a_reveal_state(rule, lm,
                                                              params):
    _, handles = serve(lm, rule, 2)
    for (n_p, n_o), h in zip(REQUESTS, handles):
        args = (params, prompt_of(n_p), h.result(), h.reveal_passes, CONFIG,
                4, MASK)
        assert np.max(np.abs(ref.reveal_logprobs(*args, pad_rows=16)
                             - ref.reveal_logprobs_naive(*args))) < ATOL


def test_two_slots_at_different_passes_share_one_step(lm):
    eng = GenerationEngine(lm, slots=2, max_len=64, denoising_steps=4)
    eng.submit(GenerationRequest(prompt_of(8), max_new_tokens=8))
    eng.step()
    eng.step()
    eng.submit(GenerationRequest(prompt_of(9), max_new_tokens=8))
    seen = set()
    while eng.step():
        if eng._active.all():
            seen.add(tuple(eng._blk_pass))
    assert any(a != b for a, b in seen)
    assert eng.stats()["decode_executables"] == 1


def test_scheduling_is_invisible_in_tokens_and_logprobs(lm):
    """Six requests through two slots, greedy and sampled, give what each
    gives alone in a fresh engine (per-request keys, row-independent
    math); so does a pool so small that slots are preempted."""
    _, together = serve(lm, "low_confidence_static", 2, sampled=(4, 9, 8))
    _, squeezed = serve(lm, "low_confidence_static", 2, sampled=(4, 9, 8),
                        block_size=4, kv_blocks=8)
    for i, h in enumerate(together):
        eng = GenerationEngine(lm, slots=2, max_len=64, logprobs=True,
                               denoising_steps=2,
                               remasking="low_confidence_static")
        r = h.request
        alone = eng.submit(GenerationRequest(
            r.prompt_ids, max_new_tokens=r.max_new_tokens,
            sampling=r.sampling))
        eng.run_until_idle()
        assert alone.result() == h.result() == squeezed[i].result()
        np.testing.assert_allclose(alone.logprobs(), h.logprobs(),
                                   atol=ATOL)


def test_a_small_pool_preempts_and_still_finishes(lm):
    eng, handles = serve(lm, "sequential", 4, block_size=4, kv_blocks=8)
    assert eng.stats()["preempted"] > 0
    assert [len(h.result()) for h in handles] == [o for _, o in REQUESTS]


@pytest.mark.parametrize("steps,per_token", [(4, 1.25), (2, 0.75), (1, 0.5)])
def test_passes_per_token_follow_the_closed_form(steps, per_token, lm):
    """`denoise_passes_per_token.serve`'s closed form against the engine's
    counters; for whole blocks it is (steps + 1) / 4 a token less the
    last block's commit."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("dppt", os.path.join(
        REPO, "chipbench", "layer_metrics",
        "denoise_passes_per_token.serve.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    eng, _ = serve(lm, "sequential", steps)
    got = eng.stats()["block_diffusion"]
    assert got["tokens_streamed"] == sum(o for _, o in REQUESTS)
    assert got["passes"] / got["tokens_streamed"] == pytest.approx(
        reader.closed_form(REQUESTS, 4, steps))
    whole = [(8, 16), (4, 400)]
    want = (per_token * 416 - 2) / 416
    assert reader.closed_form(whole, 4, steps) == pytest.approx(want)


def test_expert_shares_add_up_to_the_whole_layer(params):
    """The layer told to hold experts 0-3 and the one told to hold 4-7:
    both route over all 8, and their outputs add up to the whole layer's
    (and each equals the reference's share)."""
    cfg = MoEDecoderConfig.tiny()
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64))
    whole, counts = GatedExperts(cfg, key)(x)
    parts = []
    for held in (range(4), range(4, 8)):
        layer = GatedExperts(cfg, key, experts=held)
        assert layer.w_gate.shape == (4, 64, 32)
        y, c = layer(x)
        np.testing.assert_array_equal(c, counts)    # routing over all 8
        p = {"experts." + k: v.data for k, v in layer.state_dict().items()}
        want = ref.experts(x, p, CONFIG, held=tuple(held))
        assert float(jnp.max(jnp.abs(y - want))) < ATOL
        parts.append(y)
    assert float(jnp.max(jnp.abs(parts[0] + parts[1] - whole))) < ATOL
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-3    # a real share
    assert int(counts.sum()) == 24 * 2


def test_no_token_is_dropped_when_all_choose_one_expert():
    cfg = MoEDecoderConfig.tiny()
    layer = GatedExperts(cfg, jax.random.PRNGKey(2))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (40, 64))) + 0.5
    layer.router.data = layer.router.data.at[:, 5].set(1.0)
    y, counts = layer(x, live=jnp.arange(40) < 32)
    assert int(counts[5]) == 32 and int(counts.sum()) == 64
    p = {"experts." + k: v.data for k, v in layer.state_dict().items()}
    assert float(jnp.max(jnp.abs(y - ref.experts(x, p, CONFIG)))) < ATOL
    # every row got expert 5's product: none is zero
    assert float(jnp.min(jnp.max(jnp.abs(y), axis=1))) > 1e-4


def test_the_cache_holds_the_kv_heads_in_the_weights_type():
    lm = MoEDecoderLM.seeded(MoEDecoderConfig.tiny(dtype="bfloat16"), 0)
    eng = GenerationEngine(lm, slots=2, max_len=32)
    cache = eng.stats()["cache"]
    assert cache["heads"] == 2 and cache["dtype"] == "bfloat16"
    assert eng.cache.arrays()[0].shape[-1] == 2 * 16
    out = eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=6)
    assert len(out[0]) == 6


@pytest.mark.parametrize("kw", [
    {"denoising_steps": 3}, {"remasking": "random"}, {"paged": False},
    {"prefix_cache": True}, {"prefill_chunk": 6}, {"max_len": 62},
    {"prefill_chunk": 24}])
def test_block_diffusion_refuses_what_it_cannot_serve(kw, lm):
    """A chunk is whole blocks (6 is not) and chunks tile the slot's
    positions (24 does not divide 64)."""
    base = dict(slots=2, max_len=64)
    base.update(kw)
    with pytest.raises(ValueError):
        GenerationEngine(lm, **base)


def test_an_autoregressive_model_takes_no_denoising_steps():
    from paddle_tpu.fluid import dygraph

    with dygraph.guard():
        tlm = models.TransformerLM(models.TransformerLMConfig.tiny())
    with pytest.raises(ValueError):
        GenerationEngine(tlm, slots=2, max_len=32, denoising_steps=2)


@pytest.mark.parametrize("rule,want", [
    ("sequential", [[0, 1, 1, 0], [0, 0, 0, 0], [1, 1, 0, 0]]),
    ("low_confidence_static", [[0, 0, 1, 1], [0, 0, 0, 0], [1, 1, 0, 0]]),
    ("low_confidence_dynamic", [[0, 0, 1, 1], [0, 0, 0, 0], [1, 1, 0, 0]]),
])
def test_choose_reveals(rule, want):
    """Two a pass: the leftmost masked; the most confident (row 2: 0.6,
    then 0.4 twice, the tie to the left); a block with nothing masked (a
    commit pass) reveals nothing."""
    masked = jnp.asarray([[0, 1, 1, 1], [0, 0, 0, 0], [1, 1, 1, 1]], bool)
    lps = jnp.log(jnp.asarray([[0.9, 0.1, 0.3, 0.2], [0.5, 0.5, 0.5, 0.5],
                               [0.4, 0.6, 0.05, 0.4]]))
    got = choose_reveals(masked, lps, 2, rule, 0.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, bool))


def test_the_dynamic_rule_adds_every_position_over_the_threshold():
    masked = jnp.ones((1, 4), bool)
    lps = jnp.log(jnp.asarray([[0.7, 0.1, 0.8, 0.6]]))
    got = choose_reveals(masked, lps, 1, "low_confidence_dynamic", 0.5)
    np.testing.assert_array_equal(np.asarray(got), [[True, False, True, True]])


def test_block_counters_and_stats(lm):
    eng, _ = serve(lm, "sequential", 4)
    snap = eng.metrics_registry.snapshot()

    def value(name):
        return sum(s["value"] for s in snap[name]["series"]
                   if s["labels"]["engine"] == eng._engine)

    got = eng.stats()["block_diffusion"]
    assert value("generation_block_passes_total") == got["passes"]
    assert value("generation_block_commits_total") == got["commits"]
    assert value("generation_tokens_revealed_total") == \
        value("generation_tokens_total") == got["tokens_streamed"]
    assert value("generation_moe_experts_touched_total") > 0
    load = [s for s in snap["generation_moe_load_max_over_mean"]["series"]
            if s["labels"]["engine"] == eng._engine][0]
    assert load["count"] == eng.stats()["decode_steps"]
    assert 1.0 <= load["sum"] / load["count"] <= 8.0


PARENT_DECODE_SHA256 = {
    "paged": "0e09655013f9db53dfb61ff8648367f19e5d8d8693697a72477c71da0b857cc3",
    "dense": "71579530523454f87e876e99f0d383e94e55ce302effe7eb933a509d0024233b",
    "int8": "a97cd3c34c62fc492358006e1fb2fff7fb8f68b260b4be0317884b0a3fb7f87b",
}


@pytest.mark.parametrize("kind,kw", [("paged", {}), ("dense", {"paged": False}),
                                     ("int8", {"kv_dtype": "int8"})])
def test_an_autoregressive_models_decode_step_is_the_parents_program(kind, kw):
    """`generation_decode`'s body, the step the dispatch's program wraps
    (`_advance_step` adds the advance of lengths and steps beside it),
    of `TransformerLM.tiny()` lowers to the text it had before grouped
    heads and the mask granule came to the shared walk (sha256 of
    ``as_text(debug_info=False)`` on the parent commit, 3a4365c, under
    `tests/conftest.py`, jax 0.9.0: the one installation this repo
    supports)."""
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.generation.engine import _named

    with dygraph.guard():
        np.random.seed(0)
        tlm = models.TransformerLM(models.TransformerLMConfig.tiny())
    eng = GenerationEngine(tlm, slots=4, max_len=64, **kw)
    body = jax.jit(_named(eng._make_decode_fn(), "generation_decode"),
                   donate_argnums=eng._donate_kv)
    with eng._lock, _TRACE_LOCK:
        text = body.lower(*eng._decode_operands()).as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_DECODE_SHA256[kind]


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("granule", [1, 4])
def test_the_walk_reads_grouped_heads_under_the_mask_granule(paged, granule):
    """`cached_attention` with 8 query heads on 2 cache heads and rows
    that see their whole mask block, against the plain split-head
    reference over the view (float32 sums in another order: 1e-5)."""
    from paddle_tpu.ops import cached_attention as ca

    n, c, h, g, d, t, bs = 3, 4, 8, 2, 16, 64, 16
    rng = np.random.RandomState(granule)
    q = jnp.asarray(rng.randn(n, c, h, d), jnp.float32)
    k_new = jnp.asarray(rng.randn(n, c, g, d), jnp.float32)
    v_new = jnp.asarray(rng.randn(n, c, g, d), jnp.float32)
    pos = jnp.asarray([8, 20, 4], jnp.int32)
    live = np.asarray([True, True, False])
    if paged:
        pools = [jnp.asarray(rng.randn(1 + n * t // bs, bs, g * d),
                             jnp.float32) for _ in range(2)]
        tables = jnp.asarray(np.where(
            live[:, None], 1 + np.arange(n * t // bs).reshape(n, -1), 0),
            jnp.int32)
        ctx, (k, v) = ca.cached_attention(
            q, k_new, v_new, (*pools, pos, tables, bs), granule=granule)
        k, v = (ca.paged_gather_kv(a, tables) for a in (k, v))
    else:
        arrays = [jnp.asarray(rng.randn(n, t, g * d), jnp.float32)
                  for _ in range(2)]
        ctx, (k, v) = ca.cached_attention(
            q, k_new, v_new, (*arrays, pos, jnp.asarray(live)),
            granule=granule)
    want = ca.chunked_attention_reference(
        q, k.reshape(n, t, g, d), v.reshape(n, t, g, d), pos,
        granule=granule)
    assert float(jnp.max(jnp.abs(ctx[:2] - want[:2]))) < ATOL
    assert float(jnp.max(jnp.abs(ctx[2]))) == 0.0       # the dead slot
    if granule == 4:    # the block's first row saw its last
        causal = ca.chunked_attention_reference(
            q, k.reshape(n, t, g, d), v.reshape(n, t, g, d), pos)
        assert float(jnp.max(jnp.abs(causal[:2, 0] - want[:2, 0]))) > 1e-3
        np.testing.assert_allclose(causal[:2, 3], want[:2, 3], atol=ATOL)


def test_a_seeded_router_is_drawn_wider_than_the_other_weights():
    """`ROUTER_SPREAD` times the other weights' standard deviation (512
    draws of the router against 16,384 of a gate: 10% and 5% of room),
    so that the first chosen expert outweighs the last; the weights of
    the chosen still sum to 1."""
    from paddle_tpu.models.moe_decoder import ROUTER_SPREAD

    cfg = MoEDecoderConfig.tiny()
    layer = GatedExperts(cfg, jax.random.PRNGKey(4))
    gate = float(np.std(np.asarray(layer.w_gate.data)))
    router = float(np.std(np.asarray(layer.router.data)))
    assert abs(gate / cfg.initializer_range - 1) < 0.05
    assert abs(router / (ROUTER_SPREAD * cfg.initializer_range) - 1) < 0.1
    top_p, _ = layer.route(jax.random.normal(jax.random.PRNGKey(0),
                                             (64, 64)) * 8)
    assert float(jnp.mean(top_p[:, 0])) > float(jnp.mean(top_p[:, -1])) + 0.2
    np.testing.assert_allclose(np.asarray(top_p.sum(-1)), 1.0, atol=1e-6)


@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
def test_a_prompt_prefilled_in_chunks_scores_as_the_reference(chunk, lm,
                                                              params):
    """A prompt of 38 tokens goes into the cache ``chunk`` rows a
    scheduler iteration (its last chunk padded; 64: one chunk, mostly
    padding) while the other slot's stream runs on between the chunks;
    both streams score as the reference's reveal states."""
    eng = GenerationEngine(lm, slots=2, max_len=64, logprobs=True,
                           denoising_steps=4, prefill_chunk=chunk)
    first = eng.submit(GenerationRequest(prompt_of(9), max_new_tokens=12))
    eng.step()
    second = eng.submit(GenerationRequest(prompt_of(38), max_new_tokens=7))
    before, both = len(first._tokens), 0
    while eng.step():
        both += eng.occupancy()["chunking"] and eng.occupancy()["active"]
    assert both == -(-36 // chunk) - 1      # steps with a chunk AND a pass
    assert len(first._tokens) == 12 > before
    for h, n_p in ((first, 9), (second, 38)):
        want = ref.reveal_logprobs(params, prompt_of(n_p), h.result(),
                                   h.reveal_passes, CONFIG, 4, MASK,
                                   pad_rows=8)
        assert np.max(np.abs(np.asarray(h.logprobs()) - want)) < ATOL
    assert eng.stats()["executables"]["chunk"] == {chunk: 1}
    assert eng.stats()["executables"]["prefill"] == {}


def _solo(lm, r, **kw):
    """``r`` served alone in a fresh engine: its tokens, log-probabilities
    and the pass that revealed each token."""
    eng = GenerationEngine(lm, slots=2, max_len=64, logprobs=True, **kw)
    h = eng.submit(GenerationRequest(
        r.prompt_ids, max_new_tokens=r.max_new_tokens, sampling=r.sampling,
        stop_token_ids=r.stop_token_ids))
    eng.run_until_idle()
    return h.result(), h.logprobs(), h.reveal_passes


def _assert_solo(lm, handles, **kw):
    for h in handles:
        tokens, lps, passes = _solo(lm, h.request, **kw)
        assert h.result() == tokens and h.reveal_passes == passes
        np.testing.assert_allclose(h.logprobs(), lps, rtol=0, atol=1e-6)


@pytest.mark.parametrize("rule", REMASKING_RULES)
def test_a_pass_in_flight_serves_each_request_as_alone(rule, lm):
    """Six requests through two slots, one pass in flight: pass t+1 is
    dispatched on the device's copy of pass t's block before pass t is
    fetched.  Every stream is what it is alone, token, log-probability
    and reveal pass; every pass but those dispatched with nothing
    un-fetched overlaps the one before; under the rules that reveal a
    fixed number a pass the last one is known at dispatch and no row
    is computed for a finished request (the dynamic rule's extra
    reveals end some a pass early: that row is dropped)."""
    kw = dict(denoising_steps=2, remasking=rule, confidence_threshold=0.0085)
    eng = GenerationEngine(lm, slots=2, max_len=64, logprobs=True, **kw)
    handles = []
    for n_p, n_o in REQUESTS:
        sp = SamplingParams(temperature=0.7 if n_p in (4, 9) else 0.0,
                            top_k=5, top_p=0.9, seed=n_p)
        handles.append(eng.submit(GenerationRequest(
            prompt_of(n_p), max_new_tokens=n_o, sampling=sp)))
    alone = 0                       # passes dispatched with none in flight
    while True:
        idle, steps = eng._in_flight is None, eng._decode_steps
        if not eng.step():
            break
        alone += idle and eng._decode_steps > steps
    st = eng.stats()
    assert st["decode_overlapped"] == st["decode_steps"] - alone
    assert alone == 1               # the first: the slots never both idle
    assert eng._in_flight is None and st["executables"]["decode_step"] == 1
    # the dynamic rule may end a request a pass before the host foresaw
    assert (st["decode_rows_discarded"] > 0) == (
        rule == "low_confidence_dynamic")
    if rule == "sequential":
        for (n_p, n_o), h in zip(REQUESTS, handles):
            assert h.reveal_passes == ref.sequential_passes(n_p, n_o, 4, 2)
    _assert_solo(lm, handles, **kw)


def test_a_steady_batch_of_passes_overlaps_every_pass_but_the_first(lm):
    """Four requests shorter than a block (no chunk), all admitted by
    the first iteration: every later pass is dispatched while the one
    before is un-fetched, `generation_itl_ms` has one observation a
    pass, and the passes follow the closed form."""
    eng = GenerationEngine(lm, slots=4, max_len=64, denoising_steps=4)
    handles = [eng.submit(GenerationRequest([7 + i, 8, 9], max_new_tokens=13))
               for i in range(4)]
    eng.run_until_idle()
    assert [len(h.result()) for h in handles] == [13] * 4
    st = eng.stats()
    # 1 + 4 + 4 + 4 positions: 1 + 4 + 4 + 4 reveals and three commits
    assert st["decode_steps"] == 16
    assert st["decode_overlapped"] == st["decode_steps"] - 1
    assert st["decode_rows_discarded"] == 0
    assert eng._m_itl.summary()["count"] == st["decode_steps"]
    assert st["block_diffusion"]["passes"] == 4 * 16
    assert st["block_diffusion"]["commits"] == 4 * 3
    # the host's rows go in through one small program a trace can name
    assert eng._merge_state_fn.__name__ == "generation_state_merge"
    assert eng._jit_cache_size(eng._merge_state_fn) == 1


def test_a_slot_preempted_with_its_pass_in_flight_restarts_clean(lm):
    """Preempted while its pass is un-fetched and admitted again into
    the SAME slot before that pass is delivered: the row is dropped and
    the restarted stream holds no token of its former life."""
    req = GenerationRequest(prompt_of(9), max_new_tokens=7,
                            sampling=SamplingParams(temperature=0.7, top_k=5,
                                                    top_p=0.9, seed=9))
    eng = GenerationEngine(lm, slots=1, max_len=64, logprobs=True,
                           denoising_steps=2)
    h = eng.submit(req)
    for _ in range(3):
        assert eng.step()
    assert len(h._tokens) > 0
    (slot, before), = eng._in_flight[1]
    with eng._lock:
        eng._preempt_slot(slot, "test")
    assert eng.step()               # admits it again, same slot
    assert eng._slot_state[slot] is not before
    eng.run_until_idle()
    events = list(h.events(timeout=1))
    last = max(i for i, e in enumerate(events) if e[0] == "restart")
    assert [e[1] for e in events[last + 1:-1]] == list(range(7))
    assert eng.stats()["decode_rows_discarded"] == 1
    assert eng.stats()["preempted"] == 1
    _assert_solo(lm, [h], denoising_steps=2)


def test_a_stop_with_a_pass_in_flight_costs_one_row_and_frees_clean(lm):
    """A stop token is known only when its pass is fetched, so its slot
    rides the pass after; that row is dropped.  The next request takes
    the freed slot and its pages (its prompt's chunk writes them after
    the dropped pass did) and is exact, and so is the stream beside."""
    def sampled(seed):
        return SamplingParams(temperature=0.7, top_k=5, top_p=0.9, seed=seed)

    probe = _solo(lm, GenerationRequest(prompt_of(6), max_new_tokens=10,
                                        sampling=sampled(6)))[0]
    # the first token from the third on that the stream has not held
    k = next(i for i in range(2, 9) if probe[i] not in probe[:i])
    long = GenerationRequest(prompt_of(11), max_new_tokens=16,
                             sampling=sampled(11))
    stopper = GenerationRequest(prompt_of(6), max_new_tokens=10,
                                sampling=sampled(6),
                                stop_token_ids=(probe[k],))
    after = GenerationRequest(prompt_of(9), max_new_tokens=7,
                              sampling=sampled(9))
    eng = GenerationEngine(lm, slots=2, max_len=64, logprobs=True)
    handles = [eng.submit(r) for r in (long, stopper, after)]
    held, reused = [], []
    while eng.step():
        st = eng._slot_state[1]
        if st is not None and st.request is stopper:
            held = list(eng._slot_blocks[1])
        if st is not None and st.request is after and not reused:
            reused = list(eng._slot_blocks[1])
    assert handles[1].result() == probe[:k + 1]
    assert handles[1].finish_reason == "stop_token"
    assert set(held) & set(reused)
    assert [e[0] for e in handles[2].events(timeout=1)].count("restart") == 0
    assert eng.stats()["decode_rows_discarded"] == 1
    assert eng.cache.pool.used_blocks == 0
    _assert_solo(lm, handles)


def test_every_pass_dispatch_holds_its_parts_and_a_merge_where_rows_changed(
        lm, monkeypatch):
    """The block loop's `generation.decode_dispatch` splits as the plain
    one's does: operands and call in every pass's dispatch, the
    `generation_block_merge` call's span exactly where the host changed
    rows (a block opened, a slot freed), the dispatch histograms one
    observation a pass and `generation_decode_steps_total` the passes."""
    from test_generation import assert_dispatch_split, recording_fresh

    from paddle_tpu.observability import trace as T
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    eng = GenerationEngine(lm, slots=2, max_len=64, denoising_steps=4,
                           metrics_registry=reg)
    fresh = recording_fresh(monkeypatch, eng, "_dispatch_block",
                            lambda: eng._changed_rows()[1])
    tr = T.enable_tracing()
    tr.clear()
    try:
        handles = [eng.submit(GenerationRequest(
            prompt_of(n_p), max_new_tokens=n_o)) for n_p, n_o in REQUESTS]
        eng.run_until_idle()
        events = tr.events()
    finally:
        T.disable_tracing()
        tr.clear()
    assert [len(h.result()) for h in handles] == [n for _, n in REQUESTS]
    assert_dispatch_split(eng, reg, events, fresh)


def test_a_pass_that_opens_and_commits_no_block_puts_nothing(lm):
    """Two live streams past their prompts: a scheduler iteration whose
    pass neither follows an opened block, a commit, a park or a free nor
    grows a slot dispatches and delivers with every host-to-device
    transfer refused; every other pass merged once."""
    eng = GenerationEngine(lm, slots=2, max_len=64, denoising_steps=4)
    for n_p in (3, 2):
        eng.submit(GenerationRequest(prompt_of(n_p), max_new_tokens=21))
    eng.step()

    def grows():
        return any(eng.cache.blocks_for(int(eng._lengths[s]) + 4)
                   > len(eng._slot_blocks[s])
                   for s in np.nonzero(eng._active)[0])

    guarded = 0
    while eng._active.any():
        if eng._changed_rows()[1].any() or grows():
            eng.step()
            continue
        with jax.transfer_guard_host_to_device("disallow"):
            eng.step()
        guarded += 1
    eng.run_until_idle()
    # a block is four reveals and a commit, and the two streams commit
    # on different passes: about three of five passes change no row
    assert guarded >= 12
    assert eng._m_merges.value == eng.stats()["decode_steps"] - guarded
