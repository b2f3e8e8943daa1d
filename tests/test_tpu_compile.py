"""The main path's kernels, compiled at real widths for a DESCRIBED TPU.

The chip's compiler is installed here and compiles for a `v5e:2x2` chip
that is described, not attached: what Mosaic or XLA:TPU refuses on the
chip it refuses here too (a `dot_general` with no non-contracting
dimension, a primitive without a TPU lowering, a block over the VMEM
limit), at no chip time.  Interpret-mode tests cannot see any of that —
two decode kernels passed every one of them and compiled for no chip
until PR 22.  Nothing runs: a compile that passes says nothing about
results or times.

Rules this file keeps (see the on-chip-measurement guide): the topology
is described inside a module-scoped fixture, never at import time, in a
`skipif` or in `parametrize` arguments — only one process may load the
TPU's library, and under xdist every worker imports every test file;
shapes and shardings are built in fixtures and tests; every compile
happens in this process; the persistent compilation cache is off around
them (an entry written for a described chip cannot be read back without
one).  All of these tests live in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'

# BERT-base attention shapes (bench.py: B=48, S=512)
B, S, H, D = 48, 512, 12, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_persistent_cache):
    """compile_for_chip(fn, (shape, dtype), ...) -> optimized HLO text of
    ``fn`` compiled for one described v5e chip."""
    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_


def test_flash_attention_forward_and_backward_bert_base(compile_for_chip):
    from paddle_tpu.ops.pallas.attention import flash_attention

    def loss(q, k, v, bias):
        out = flash_attention(q, k, v, bias=bias, layout="BSHD",
                              interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    qkv = ((B, S, H, D), jnp.bfloat16)
    hlo = compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                           qkv, qkv, qkv, ((B, 1, 1, S), jnp.float32))
    # the forward and at least one backward kernel
    assert hlo.count(CUSTOM_CALL) >= 2
    assert "transpose(jvp" in hlo


@pytest.mark.parametrize("seq", [512, 500], ids=["S512", "S500-unaligned"])
def test_flash_attention_causal_prefill(compile_for_chip, seq):
    from paddle_tpu.ops.pallas.attention import flash_attention

    qkv = ((1, seq, H, D), jnp.float32)
    hlo = compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, causal=True, layout="BSHD",
                                        interpret=False), qkv, qkv, qkv)
    assert CUSTOM_CALL in hlo


# the BERT-base FFN GEMM: [B*S, 768] x [768, 3072]
M, K, N = B * S, 768, 3072
GEMM = (((M, K), jnp.bfloat16), ((K, N), jnp.bfloat16), ((N,), jnp.bfloat16))


@pytest.mark.parametrize("act,approx", [("gelu", True), ("relu", False)],
                         ids=["gelu-tanh-form", "relu"])
def test_matmul_bias_act_kernel(compile_for_chip, act, approx):
    from paddle_tpu.ops.pallas.matmul import matmul_bias_act

    def loss(x, w, b):
        out = matmul_bias_act(x, w, b, activation=act, approximate=approx,
                              interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    hlo = compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                           *GEMM)
    assert hlo.count(CUSTOM_CALL) >= 2


def test_exact_gelu_is_routed_to_xla_not_to_a_compile_error(
        compile_for_chip, monkeypatch):
    """Mosaic has no `erf`: the op's dispatch refuses exact gelu with the
    reason counted, and the step compiles as the XLA composition."""
    from paddle_tpu.fluid.core.registry import LowerContext, get_op_def
    from paddle_tpu.ops import dispatch

    # the dispatch asks which backend is live; here that is the CPU, so
    # the test answers for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lower = get_op_def("matmul_bias_act").lower
    key = ("matmul_bias_act", "xla composition",
           "exact gelu: the Pallas TPU lowering has no erf")
    before = dispatch.choices().get(key, 0)

    def loss(x, w, b):
        with pytest.warns(UserWarning, match="no erf"):
            out = lower(LowerContext(), {"X": [x], "Y": [w], "Bias": [b]},
                        {"act_type": "gelu", "approximate": False,
                         "x_num_col_dims": 1})["Out"][0]
        return (out.astype(jnp.float32) ** 2).sum()

    hlo = compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                           *GEMM)
    assert CUSTOM_CALL not in hlo
    assert dispatch.choices()[key] > before


# GPT-2-medium's cache widths (the serving cell's): 16 heads of 64, 16
# slots of 1024 positions, blocks of 16; the depth and the vocabulary are
# cut, since only the cache handling is under test
CACHE_LAYERS = 2


@pytest.fixture(scope="module")
def cache_width_lm():
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.models.transformer_lm import (
        TransformerLM,
        TransformerLMConfig,
    )

    cfg = TransformerLMConfig(
        vocab_size=512, hidden_size=1024, num_layers=CACHE_LAYERS,
        num_heads=16, intermediate_size=1024,
        max_position_embeddings=1024, dropout=0.0)
    with dygraph.guard():
        return TransformerLM(cfg)


def _f32(shape):
    return "f32[%s]" % ",".join(map(str, shape))


def _pool_sized_writers(hlo, pool_shape, stacked_shape):
    """Lines of the optimized HLO where a `copy`, a `concatenate` or a
    `dynamic-update-slice` (alone or as a fusion's name) produces an
    array of a whole per-layer pool's shape, or of the stack of them:
    anywhere in the module, the bodies of its `while` loops included."""
    import re

    shapes = [_f32(pool_shape), _f32(stacked_shape)]
    op = re.compile(r"= (\S+?)(?:\{[^}]*\})? (copy|concatenate|"
                    r"dynamic-update-slice)\(")
    named = re.compile(r"%\S*(copy|concatenate|dynamic-update-slice)\S*"
                       r" = (\S+?)(?:\{[^}]*\})? fusion\(")
    bad = []
    for line in hlo.splitlines():
        m, f = op.search(line), named.search(line)
        shape = m.group(1) if m else f.group(2) if f else None
        if shape in shapes:
            bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_cached_steps_write_the_kv_cache_in_place(
        cache_width_lm, one_chip, no_persistent_cache, paged):
    """The decode step and a prefill, compiled for the described chip,
    hold the KV cache as donated per-layer arrays that they write in
    place: the outputs alias the whole cache, the temporaries stay under
    half of it (the parent's stacked `[L, NB, bs, H, D]` pool needed
    three times its size: each layer's pool sliced out, transposed to a
    layout a scatter can work in, written, transposed back and stacked),
    and nothing copies, concatenates or rewrites a pool-sized array.

    These are the programs the chip runs: a merged cache, dense or
    paged at any block size, is attended over as it lies, and a
    128-token prefill takes the naive attention on the chip too.

    The decode step walks the live part of the cache (`_attend_live`):
    two nested `while` loops a layer whose trip counts are data, the
    cache arrays passing through them as the donated operands they are.
    No view of slots x max_len x H*D exists (the parent gathered a
    `[1024,16,1024]` one a layer and array), and the largest array a
    loop body makes is a chunk of a group's slots."""
    import numpy as np

    from paddle_tpu import generation

    engine = generation.GenerationEngine(
        cache_width_lm, slots=16, max_len=1024, block_size=16,
        paged=paged, donate=True, logprobs=True)
    arrays = engine.cache.arrays()
    assert len(arrays) == engine._nc == 2 * CACHE_LAYERS
    pool_shape = arrays[0].shape
    assert pool_shape == ((16 * 64 + 1, 16, 1024) if paged
                          else (16, 1024, 1024))
    cache_bytes = engine.cache.describe()["bytes"]
    assert cache_bytes == sum(a.nbytes for a in arrays)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one_chip), tree)

    bucket = 128
    where = (engine.cache.table_row(0)[None].astype(np.int32) if paged
             else np.int32(0))
    programs = {
        "decode": (engine._decode_step_fn, engine._decode_operands()),
        "prefill": (engine._prefill_fns[bucket], (
            engine._params, *arrays, np.zeros((1, bucket), np.int32),
            np.int32(bucket), where, np.zeros(2, np.uint32),
            np.float32(0.0), np.int32(0), np.float32(1.0))),
    }
    for name, (fn, operands) in programs.items():
        compiled = fn.lower(*on_chip(operands)).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes, name
        assert mem.temp_size_in_bytes < cache_bytes // 2, (
            name, mem.temp_size_in_bytes, cache_bytes)
        hlo = compiled.as_text()
        bad = _pool_sized_writers(
            hlo, pool_shape, (CACHE_LAYERS,) + pool_shape)
        assert not bad, (name, bad)
        if name != "decode":
            continue
        # the walk's two loops a layer, and the sampler's two selections
        assert hlo.count(" while(") == 2 * CACHE_LAYERS + 2
        assert " sort(" not in hlo
        # slots x max_len x H*D elements: the gathered view, in either
        # order of its first two dimensions.  A dense cache IS such an
        # array, so there only its own in-place scatters may make one.
        view = {_f32((1024, 16, 1024)), _f32((16, 1024, 1024))}
        makers = re.findall(
            r"= (f32\[[\d,]+\])(?:\{[^}]*\})? ([\w\-]+)\(", hlo)
        whole = {op for shape, op in makers if shape in view}
        assert whole <= (set() if paged else
                         {"parameter", "scatter", "fusion", "bitcast",
                          "get-tuple-element"}), whole
        assert not paged or not any(v in hlo for v in view)
        # a dense cache is walked as the pool its 128-position chunks
        # make: the same bytes, never a copy of them
        assert paged or not _pool_sized_writers(
            hlo, (16 * 8, 128, 1024), (CACHE_LAYERS, 16 * 8, 128, 1024))
        # and the temporaries are a few chunks, not a view (the parent's
        # step held 25.5 MB of them at these widths and two layers)
        assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes


@pytest.mark.parametrize("form", ["bfloat16", "int8", "block128"])
def test_decode_step_compiles_for_the_other_cache_forms(
        cache_width_lm, one_chip, no_persistent_cache, form):
    """The engine's decode step for the cache forms no cell runs,
    compiled for the described chip (interpret mode and the CPU pass
    what Mosaic and XLA:TPU refuse): a bfloat16 cache (the step function
    takes the cache's type from its operands), an int8 pool with its
    scales, and blocks of 128, one a chunk of the walk.  As for the
    cell's form: the outputs alias the whole cache, two `while` loops a
    layer and the sampler's two, no kernel, temporaries of a few
    chunks."""
    import numpy as np

    from paddle_tpu import generation

    engine = generation.GenerationEngine(
        cache_width_lm, slots=16, max_len=1024,
        block_size=128 if form == "block128" else 16,
        kv_dtype="int8" if form == "int8" else None,
        donate=True, logprobs=True)
    operands = engine._decode_operands()
    nc = engine._nc
    assert nc == (4 if form == "int8" else 2) * CACHE_LAYERS
    held = {a.dtype.name for a in operands[1:1 + nc]}
    assert held == ({"int8", "float32"} if form == "int8" else {"float32"})

    def spec(a, dtype=None):
        return jax.ShapeDtypeStruct(np.shape(a), dtype or a.dtype,
                                    sharding=one_chip)

    as_cache = jnp.bfloat16 if form == "bfloat16" else None
    specs = (jax.tree_util.tree_map(spec, operands[0]),
             *(spec(a, as_cache) for a in operands[1:1 + nc]),
             *jax.tree_util.tree_map(spec, operands[1 + nc:]))
    compiled = engine._decode_step_fn.lower(*specs).compile()
    cache_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                      for s in specs[1:1 + nc])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    hlo = compiled.as_text()
    assert hlo.count(" while(") == 2 * CACHE_LAYERS + 2
    assert CUSTOM_CALL not in hlo
