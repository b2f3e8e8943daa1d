"""paddle_tpu.tune — measured compiler autotuner.

The standing mechanism behind PERF.md's measure-keep-or-reject
experiments: given a Program (or a flash-attention shape, a serving
traffic sample, a jitted train step), enumerate a candidate space of
knobs the stack already exposes, prune it with the `analysis.perf`
static roofline model, verify every surviving program rewrite with
`ir.clone_and_apply(verify=True)` (a broken candidate is excluded with
the offending pass NAMED, never timed), compile-and-time the rest
(warmup + median-of-k, compile cost attributed via the PR-4
``xla_compilations`` accumulator, PR-6 tracer spans), and persist the
winner in a `TuningCache` keyed by program hash + mesh + platform/chip
+ jax version inside the persistent compile-cache dir — so the second
run of any workload gets the tuned config (and, via jax's own
persistent cache, the tuned executable) for free.

Front ends:

* ``search(program, fetch_list, ...)`` — pass pipelines x donation
  (+ GSPMD sharding of large matmuls on an ambient mesh);
* ``search_flash_blocks(shape, ...)`` — the pallas attention
  (block_q, block_k) grid;
* ``search_gemm_blocks(m, k, n, ...)`` — the pallas fused-epilogue
  GEMM (block_m, block_n, block_k) tile grid;
* ``search_bucket_ladder(predictor, example, traffic, ...)`` — serving
  batch-bucket ladders (`InferenceServer.autotune` wires it in);
* ``search_step(build_and_time, variants, ...)`` — opaque jitted-step
  knobs;
* ``search_train_step(build_and_time, ...)`` — the distributed-step
  knobs: ZeRO stage x accumulate_steps x gather-chunk-bytes
  (``bench.py --multichip --autotune``);
* ``search_hostemb_cache(build_and_time, ...)`` — the hot-row
  device-cache capacity of a host-embedding workload
  (``benchmarks/streaming_bench.py --autotune``);
* ``search_generation_config(build_and_time, ...)`` — the decode
  engine's slot count (`paddle_tpu.generation`;
  ``benchmarks/generation_bench.py --autotune``);
* ``search_rl_config(build_and_time, ...)`` — the RL feedback loop's
  rollout-vs-train batch arbitration (`paddle_tpu.rl`;
  ``benchmarks/rl_loop_bench.py --autotune``).

Entry points: ``CompiledProgram.with_autotune()`` (Executor applies the
tuned pipeline on first run), ``InferenceServer.autotune()``,
``bench.py --multichip --autotune``, and the ``tools/autotune.py``
operator CLI.
"""

from __future__ import annotations

from .cache import (  # noqa: F401
    TUNE_SCHEMA_VERSION,
    TuningCache,
    cache_key_parts,
    default_cache_dir,
)
from .search import (  # noqa: F401
    CandidateResult,
    SearchReport,
    search,
    search_bucket_ladder,
    search_flash_blocks,
    search_gemm_blocks,
    search_generation_config,
    search_rl_config,
    search_hostemb_cache,
    search_step,
    search_train_step,
    tuned_program,
)
from .space import (  # noqa: F401
    Candidate,
    SearchSpace,
    cache_capacity_candidates,
    default_pass_pipelines,
    flash_block_candidates,
    gemm_block_candidates,
    ladder_candidates,
    rl_batch_candidates,
    sharding_candidates,
    train_step_candidates,
)

__all__ = [
    "Candidate",
    "CandidateResult",
    "SearchReport",
    "SearchSpace",
    "TUNE_SCHEMA_VERSION",
    "TuningCache",
    "cache_capacity_candidates",
    "cache_key_parts",
    "default_cache_dir",
    "default_pass_pipelines",
    "flash_block_candidates",
    "gemm_block_candidates",
    "ladder_candidates",
    "rl_batch_candidates",
    "search",
    "search_bucket_ladder",
    "search_flash_blocks",
    "search_gemm_blocks",
    "search_hostemb_cache",
    "search_rl_config",
    "search_step",
    "search_train_step",
    "sharding_candidates",
    "train_step_candidates",
    "tuned_program",
]
