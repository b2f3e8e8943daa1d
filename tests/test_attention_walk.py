"""The attend half of `cached_attention`: a walk over the LIVE part of
the cache (live slots in groups, each group in chunks of positions up
to its longest member), pinned against `merged_attention` over the full
gather it replaced, and the engine's `generation_attn_walk_share`
against its closed form.

The drills:

* **equivalence** — every cache form x rows x heads x load, lengths on
  and beside a chunk boundary, live slots scattered over the slot
  indices; a dead row is 0; the real blocks of a slot whose table row
  is zeroed (mid-chunk in the engine) are poisoned with NaN, so a
  single fetch of one would show;
* **one plan** — the host (numpy) and the device (jnp) get the same
  order and trip counts from `walk_plan`;
* **engine** — streams equal the sequential oracle's while slots
  finish, are preempted and re-admitted and others cross a chunk
  boundary; ONE decode executable from length 1 to max_len - 1.
"""

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import models
from paddle_tpu.fluid import dygraph

gen = paddle_tpu.generation

SLOTS, MAX_LEN, D = 16, 384, 8


def _walk_case(form, c, h, n_live, seed, max_len=MAX_LEN):
    """One layer's cache in ``form`` with ``n_live`` of SLOTS slots live
    at lengths around the 128-position chunk boundary, plus the queries
    and new rows of a C-row call: ``(q, k_new, v_new, cache tuple,
    live [N] bool, start [N], tables or None)``."""
    import jax.numpy as jnp

    from paddle_tpu.ops.cached_attention import quantize_kv

    rng = np.random.RandomState(seed)
    bs = {"paged16": 16, "paged128": 128, "int8": 16, "dense": None}[form]
    hd = h * D
    live = np.zeros(SLOTS, bool)
    live[rng.permutation(SLOTS)[:n_live]] = True
    lengths = np.minimum(np.resize([1, 127, 128, 129, max_len - c], SLOTS),
                         max_len - c)
    rng.shuffle(lengths)
    # the call writes rows pos..pos+c-1 and row i attends <= pos+i
    pos = np.where(live, lengths - 1, rng.randint(0, max_len - c, SLOTS))
    pos = pos.astype(np.int32)
    q, k_new, v_new = (jnp.asarray(rng.randn(SLOTS, c, h, D)
                                   .astype(np.float32)) for _ in range(3))
    if form == "dense":
        k, v = (jnp.asarray(rng.randn(SLOTS, max_len, hd)
                            .astype(np.float32)) for _ in range(2))
        return q, k_new, v_new, (k, v, jnp.asarray(pos),
                                 jnp.asarray(live)), live, pos, None
    mb = max_len // bs
    nb = 1 + SLOTS * mb
    true_tables = (1 + rng.permutation(SLOTS * mb)).reshape(SLOTS, mb)
    pools = [rng.randn(nb, bs, hd).astype(np.float32) for _ in range(2)]
    for pool in pools:
        # a dead slot keeps its blocks (the engine's mid-chunk slot):
        # the step's table operand has its row zeroed, so the walk must
        # never fetch them.  0 * NaN is NaN: one fetch would show.
        pool[true_tables[~live].ravel()] = np.nan
    tables = np.where(live[:, None], true_tables, 0).astype(np.int32)
    if form == "int8":
        quant = [quantize_kv(jnp.asarray(p.reshape(nb, bs, h, D)))
                 for p in pools]
        arrays = tuple(qv.reshape(nb, bs, hd) for qv, _ in quant) + tuple(
            s for _, s in quant)
    else:
        arrays = tuple(jnp.asarray(p) for p in pools)
    cache = arrays + (jnp.asarray(pos), jnp.asarray(tables), bs)
    return q, k_new, v_new, cache, live, pos, tables


@pytest.mark.parametrize("n_live", [0, 1, 5, 16])
@pytest.mark.parametrize("h", [16, 4], ids=["H16", "H4-shard"])
@pytest.mark.parametrize("c", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("form", ["paged16", "paged128", "dense", "int8"])
def test_walk_equals_merged_attention_over_the_full_gather(form, c, h,
                                                           n_live):
    _check_walk(form, c, h, n_live, MAX_LEN)


@pytest.mark.parametrize("form,max_len", [
    ("paged16", 192),    # 12 blocks, 8 a chunk: the last chunk is clamped
    ("dense", 192),      # cut into chunks of 96, which tile it
    ("dense", 180),      # no whole-tile chunk divides it: one chunk
    ("paged16", 48),     # shorter than a chunk
])
def test_walk_over_a_cache_its_chunks_do_not_divide(form, max_len):
    _check_walk(form, 3, 4, 5, max_len)


def _jitted_cached_attention(q, k_new, v_new, cache, tables):
    """`cached_attention` under `jit`, a paged cache's block size (the
    tuple's last entry) static as the engine has it."""
    import jax

    from paddle_tpu.ops.cached_attention import cached_attention

    tail = cache[-1:] if tables is not None else ()
    dynamic = cache[:-1] if tables is not None else cache
    return jax.jit(
        lambda q, k, v, *cc: cached_attention(q, k, v, cc + tail))(
            q, k_new, v_new, *dynamic)


def _check_walk(form, c, h, n_live, max_len):
    import jax.numpy as jnp

    from paddle_tpu.ops.cached_attention import (
        merged_attention,
        paged_gather_kv,
    )

    q, k_new, v_new, cache, live, pos, tables = _walk_case(
        form, c, h, n_live, seed=n_live + 17 * c + h, max_len=max_len)
    ctx, arrays = _jitted_cached_attention(q, k_new, v_new, cache, tables)
    ctx = np.asarray(ctx)
    assert ctx.shape == (SLOTS, c, h, D)
    if tables is None:
        k_view, v_view = arrays
    else:
        scales = arrays[2:] if form == "int8" else (None, None)
        k_view, v_view = (paged_gather_kv(a, jnp.asarray(tables), s)
                          for a, s in zip(arrays[:2], scales))
    start = jnp.where(jnp.asarray(live), jnp.asarray(pos), -c)
    want = np.asarray(merged_attention(q, k_view, v_view, start))
    assert np.isfinite(ctx).all()      # no poisoned block was fetched
    np.testing.assert_allclose(ctx, want, rtol=1e-5, atol=1e-5)
    assert not ctx[~live].any()        # a dead row returns 0
    assert ctx[live].any(axis=(1, 2, 3)).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("c", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("form", ["dense", "paged16", "paged128", "int8"])
def test_cached_attention_equals_the_split_heads_references(form, c, dtype):
    """`cached_attention` against the plain references, which share no
    code with it (split heads, the whole view, one softmax): one token
    a slot against `decode_attention_reference` /
    `paged_decode_attention_reference`, five rows against
    `chunked_attention_reference`, over what the call left in the cache;
    queries, new rows and a float cache in ``dtype`` (an int8 pool keeps
    its float32 scales)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.cached_attention import (
        chunked_attention_reference,
        decode_attention_reference,
        paged_decode_attention_reference,
        paged_gather_kv,
    )

    h, n_live = 4, 5
    q, k_new, v_new, cache, live, pos, tables = _walk_case(
        form, c, h, n_live, seed=3 + c)
    q, k_new, v_new = (x.astype(dtype) for x in (q, k_new, v_new))
    if form != "int8":
        cache = tuple(a.astype(dtype) for a in cache[:2]) + cache[2:]
    ctx, arrays = _jitted_cached_attention(q, k_new, v_new, cache, tables)
    assert ctx.dtype == q.dtype and ctx.shape == (SLOTS, c, h, D)
    assert all(a.dtype == b.dtype for a, b in zip(arrays, cache))

    def split(a):
        return a.reshape(a.shape[:2] + (h, D))

    k, v = split(arrays[0]), split(arrays[1])
    scales = dict(k_scale=arrays[2], v_scale=arrays[3]) \
        if form == "int8" else {}
    lengths = jnp.asarray(np.where(live, pos + 1, 0).astype(np.int32))
    if c == 1 and tables is None:
        want = decode_attention_reference(q[:, 0], k, v, lengths)[:, None]
    elif c == 1:
        want = paged_decode_attention_reference(
            q[:, 0], k, v, jnp.asarray(tables), lengths, **scales)[:, None]
    else:
        if tables is not None:
            k = paged_gather_kv(k, jnp.asarray(tables), scales.get("k_scale"))
            v = paged_gather_kv(v, jnp.asarray(tables), scales.get("v_scale"))
        want = chunked_attention_reference(
            q, k, v, jnp.asarray(np.where(live, pos, -c).astype(np.int32)))
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (ctx, want))
    assert np.isfinite(got).all()
    tol = 1e-5 if dtype == "float32" else 2e-2    # one bfloat16 rounding
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert not got[~live].any() and got[live].any(axis=(1, 2, 3)).all()


@pytest.mark.parametrize("extent", [
    [0] * 16,
    [0, 0, 301, 0, 0, 0, 101, 0, 0, 0, 0, 0, 0, 0, 0, 0],
    [128, 129, 1, 1024, 0, 640, 128, 7, 0, 0, 513, 512, 90, 300, 2, 128],
    [5, 9, 2],                        # fewer slots than a group, padded
    list(range(1, 8)),                # 7 slots: the last group is padded
])
def test_walk_plan_is_the_same_on_the_host_and_on_the_device(extent):
    """`walk_plan` is the one definition of the loops' trip counts: the
    same order, inverse and chunk counts from numpy and from jnp, the
    longest first, each group walking to its longest member."""
    import jax.numpy as jnp

    from paddle_tpu.ops.cached_attention import walk_plan

    group, chunk = 4, 128
    ext = np.asarray(extent, np.int32)
    host = walk_plan(ext, group, chunk, xp=np)
    dev = walk_plan(jnp.asarray(ext), group, chunk)
    for a, b in zip(host, dev):
        np.testing.assert_array_equal(a, np.asarray(b))
    order, rank, chunks = host
    n = len(order)
    assert n % group == 0 and n - len(ext) < group
    padded = np.concatenate([ext, np.zeros(n - len(ext), np.int32)])
    assert sorted(order) == list(range(n))
    assert (np.diff(padded[order]) <= 0).all()          # longest first
    np.testing.assert_array_equal(order[rank], np.arange(n))
    want = [-(-int(padded[order[g * group:(g + 1) * group]].max()) // chunk)
            for g in range(n // group)]
    assert list(chunks) == want
    live_groups = -(-int((ext > 0).sum()) // group)
    assert int((chunks > 0).sum()) == live_groups


def test_attention_walk_share_closed_form():
    from paddle_tpu.ops.cached_attention import (
        _BLOCK_DIAGONAL_ROWS,
        attention_walk_share,
        walk_geometry,
    )

    group, chunk = walk_geometry(16, 1024, 16)
    assert (group, chunk) == walk_geometry(16, 1024)
    assert 1024 % chunk == 0 and chunk % 16 == 0 and 16 % group == 0
    # a cache smaller than a chunk or a group is walked whole
    assert walk_geometry(3, 48, 16) == (3, 48)
    # a dense cache is cut into chunks that tile it in whole (8, 128)
    # tiles of rows, or walked as one chunk where nothing does
    assert walk_geometry(4, 160) == (4, 80)
    assert walk_geometry(4, 1000) == (4, 40)
    assert walk_geometry(4, 60) == (4, 60)
    ext = np.zeros(16, np.int32)
    assert attention_walk_share(ext, 16, 1024, 16) == 0.0
    ext[[3, 11]] = [301, 101]
    # two live slots: one group, to the longer one's chunks
    want = group * chunk * -(-301 // chunk) / (16 * 1024)
    assert attention_walk_share(ext, 16, 1024, 16) == want
    assert attention_walk_share(ext, 16, 1024) == want    # dense
    # everything live to the end: the whole cache, and never past it
    full = np.full(16, 1024, np.int32)
    assert attention_walk_share(full, 16, 1024, 16) == 1.0
    assert attention_walk_share(full + 9, 16, 1024, 16) == 1.0
    # a call wider than the block-diagonal form reads the whole view
    assert attention_walk_share(ext, _BLOCK_DIAGONAL_ROWS + 1, 1024, 16) == 1.0


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

CFG = models.TransformerLMConfig(
    vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
    intermediate_size=64, max_position_embeddings=512, dropout=0.0)


@pytest.fixture(scope="module")
def lm():
    with dygraph.guard():
        np.random.seed(3)
        return models.TransformerLM(CFG)


def _engine(model, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 192)
    kw.setdefault("prefill_buckets", [16, 128])
    return gen.GenerationEngine(model, **kw)


def test_engine_streams_equal_the_oracle_across_a_chunk_boundary(lm):
    """Requests of 100 to 126 prompt tokens decode past position 128
    (the walk's second chunk) on a pool too small for all of them:
    slots finish at staggered times, are preempted and re-admitted
    while the others cross the boundary, and every stream, greedy or
    sampled, equals the one-request-at-a-time oracle's."""
    rng = np.random.RandomState(2)
    reqs = []
    for i, plen in enumerate([126, 100, 118, 124, 109, 121]):
        sp = (gen.SamplingParams.greedy() if i % 2 == 0 else
              gen.SamplingParams(temperature=0.9, top_k=20, top_p=0.9,
                                 seed=300 + i))
        reqs.append(gen.GenerationRequest(
            rng.randint(0, CFG.vocab_size, plen),
            max_new_tokens=[12, 40, 25, 8, 33, 18][i], sampling=sp,
            request_id="w%d" % i))
    # 23 usable blocks of 16: three prompts fit, their growth does not
    eng = _engine(lm, kv_blocks=24)
    handles = [eng.submit(r) for r in reqs]
    crossed = set()
    while eng.step():
        live = np.nonzero(eng._active)[0]
        if len(live) > 1:
            crossed.update(int(x) > 128 for x in eng._lengths[live])
    got = [h.result() for h in handles]
    assert [len(t) for t in got] == [r.max_new_tokens for r in reqs]
    assert crossed == {False, True}     # both sides of the boundary
    assert eng.stats()["preempted"] >= 1
    assert eng.cache.pool.used_blocks == 0
    assert eng._decode_cache_size() == 1
    assert got == gen.sequential_oracle(lambda: _engine(lm), reqs)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_one_decode_executable_from_length_1_to_max_len(lm, paged):
    """The walk's extent is data: a request that grows from one token
    to max_len - 1 beside shorter ones runs ONE decode executable."""
    eng = _engine(lm, max_len=160, paged=paged)
    long = eng.submit(gen.GenerationRequest([7], max_new_tokens=159))
    short = [eng.submit(gen.GenerationRequest(
        list(range(1, 6 + i)), max_new_tokens=20 + 30 * i))
        for i in range(3)]
    eng.run_until_idle()
    assert len(long.result()) == 159
    assert [len(h.result()) for h in short] == [20, 50, 80]
    assert int(eng._lengths.max()) == 159
    assert eng._decode_cache_size() == 1
    assert eng.stats()["executables"]["decode_step"] == 1


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_engine_walk_share_is_the_closed_form(lm, paged):
    """`generation_attn_walk_share`, once a decode step: G x chunks x L
    positions over slots x positions, for the lengths the step ran at;
    `engine.stats()` reports its mean."""
    from paddle_tpu.ops.cached_attention import walk_geometry

    slots, max_len, plen, new = 8, 512, 120, 20
    eng = _engine(lm, slots=slots, max_len=max_len, paged=paged)
    assert eng.stats()["attn_walk_share"] is None
    h = eng.submit(gen.GenerationRequest(list(range(1, plen + 1)),
                                         max_new_tokens=new))
    eng.run_until_idle()
    assert len(h.result()) == new
    group, chunk = walk_geometry(slots, max_len, eng.block_size)
    # the prefill gave token 0; step i decodes at length plen + i and
    # attends plen + i + 1 positions: one slot live, so one group
    want = [group * chunk * -(-(plen + i + 1) // chunk) / (slots * max_len)
            for i in range(new - 1)]
    assert len(set(want)) == 2          # the request crossed a chunk
    summary = eng._m_walk.summary()
    assert summary["count"] == new - 1
    assert summary["min"] == min(want) and summary["max"] == max(want)
    assert eng.stats()["attn_walk_share"] == pytest.approx(
        sum(want) / len(want))
    assert eng.stats()["attn_walk_share"] < 0.3
