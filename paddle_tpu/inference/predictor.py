"""AnalysisPredictor equivalent: load-once, compile-once, serve-many.

Capability parity: reference `inference/api/analysis_predictor.cc`
(AnalysisPredictor::Run), `api/paddle_api.h` (AnalysisConfig), and
`framework/naive_executor.cc` (per-request runs without scope churn).
"""

from __future__ import annotations

import os

import numpy as np


class AnalysisConfig:
    """cf. reference AnalysisConfig: model path + tuning toggles.  GPU/MKLDNN
    toggles are accepted for parity; device selection is jax's backend."""

    def __init__(self, model_dir=None, prog_file=None, params_file=None):
        self.model_dir = model_dir
        self.prog_file = prog_file
        self.params_file = params_file
        self._use_tpu = True
        self._memory_optim = True
        self._int8 = False
        self._compile_cache_dir = None

    def enable_use_gpu(self, memory_pool_mb=100, device_id=0):
        pass  # device comes from the jax backend (TPU/CPU)

    def disable_gpu(self):
        pass

    def switch_ir_optim(self, flag=True):
        self._memory_optim = flag  # XLA always optimizes; recorded

    def enable_memory_optim(self):
        self._memory_optim = True

    def enable_compilation_cache(self, cache_dir=None):
        """Persist compiled executables across process restarts (jax's
        persistent compilation cache): a server restart re-loads the
        bucket-ladder executables from disk instead of recompiling them.
        cf. the executor's in-process program cache — this is its
        on-disk, cross-restart analogue for the serving path.

        NOTE: jax's cache is process-global, so creating a Predictor
        from this config enables on-disk caching for EVERY compile in
        the process (with the size/compile-time thresholds zeroed).
        Intended for dedicated serving processes."""
        from ..fluid.core.compile_cache import compile_cache_dir

        self._compile_cache_dir = cache_dir or compile_cache_dir()

    def enable_int8(self):
        """Weight-only int8 on load (cf. reference
        EnableTensorRtEngine(precision=Int8) / mkldnn_quantizer): matmul
        and conv weights are stored int8 and dequantize in-graph, so they
        stream from HBM at 1/4 bandwidth."""
        self._int8 = True


class Predictor:
    """Compile-once server runner (cf. AnalysisPredictor + NaiveExecutor)."""

    def __init__(self, config: AnalysisConfig):
        import jax

        from ..fluid import framework, io
        from ..fluid.core.block_eval import run_ops
        from ..fluid.core.registry import LowerContext

        self._config = config
        if config._compile_cache_dir:
            from ..fluid.core.compile_cache import enable_compile_cache

            enable_compile_cache(config._compile_cache_dir)
            # a serving ladder is many small executables: cache them all
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", 0)
        from ..fluid.executor import Executor
        from ..fluid.core.scope import Scope

        self._scope = Scope()
        exe = Executor()
        import contextlib

        from ..fluid.executor import scope_guard

        with scope_guard(self._scope):
            program, feeds, fetches = io.load_inference_model(
                config.model_dir, exe,
                model_filename=config.prog_file,
                params_filename=config.params_file,
            )
        self._program = program
        self._feed_names = feeds
        self._fetch_names = [
            f.name if hasattr(f, "name") else f for f in fetches
        ]
        if config._int8:
            from ..fluid.contrib.slim.quantization import (
                PostTrainingQuantization,
            )

            program = PostTrainingQuantization(
                executor=exe, program=program, feed_names=feeds,
                scope=self._scope, batch_generator=None,
                quantize_activations=False,  # weight-only without calib data
            ).quantize()
            # the int8 rewrite is a program mutation like any IR pass:
            # re-verify before compiling against it (the load-time check
            # above only saw the fp32 program)
            from ..fluid.io import _verify_io_program

            _verify_io_program(
                program, list(feeds), list(self._fetch_names),
                "int8-quantized inference program")
            self._program = program
        block = program.global_block
        ops = block.ops
        # device-resident weights, loaded once (zero per-request transfer).
        # Only names the (possibly int8-rewritten) program actually reads —
        # after enable_int8 the fp32 originals must NOT occupy HBM.
        referenced = set()
        for op in ops:
            referenced.update(op.all_input_names())
        self._weights = {
            name: jax.device_put(self._scope.find_var(name))
            for name in self._scope.local_names()
            if name in referenced and self._scope.find_var(name) is not None
        }

        def run_pure(weights, feed_vals):
            env = dict(weights)
            env.update(feed_vals)
            ctx = LowerContext(base_key=None, is_test=True)
            run_ops(ops, env, ctx)
            return [env[n] for n in self._fetch_names]

        self._jitted = jax.jit(run_pure)
        self._signatures = set()
        self._costs = {}     # feed signature -> cost_analysis dict

    # -- reference-style API -------------------------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def _prepare_feed(self, inputs):
        """Validate + normalize inputs into {name: np.ndarray}.  A list
        must match feed order/length exactly; a dict must carry exactly
        the declared feeds (zip used to drop extras silently)."""
        if isinstance(inputs, dict):
            unknown = sorted(set(inputs) - set(self._feed_names))
            missing = sorted(set(self._feed_names) - set(inputs))
            if unknown or missing:
                raise ValueError(
                    "feed dict mismatch: expects feeds %s%s%s"
                    % (self._feed_names,
                       ("; missing %s" % missing) if missing else "",
                       ("; unknown %s" % unknown) if unknown else ""))
            return {k: np.asarray(v) for k, v in inputs.items()}
        inputs = list(inputs)
        if len(inputs) != len(self._feed_names):
            raise ValueError(
                "feed list length mismatch: expects %d feeds %s, got %d"
                % (len(self._feed_names), self._feed_names, len(inputs)))
        return {n: np.asarray(v) for n, v in zip(self._feed_names, inputs)}

    def _note_signature(self, feed_vals):
        self._signatures.add(tuple(
            (k, v.shape, str(v.dtype)) for k, v in sorted(feed_vals.items())))

    @property
    def compile_count(self):
        """Number of XLA executables built by this predictor (one per
        distinct feed signature) — the serving-path compile-storm gauge."""
        try:
            return int(self._jitted._cache_size())
        except Exception:
            return len(self._signatures)

    def run(self, inputs):
        """inputs: list of arrays (feed order) or {name: array}.
        Returns list of numpy arrays in fetch order."""
        feed_vals = self._prepare_feed(inputs)
        self._note_signature(feed_vals)
        outs = self._jitted(self._weights, feed_vals)
        return [np.asarray(o) for o in outs]

    def run_async(self, inputs):
        """Like run() but returns the jitted call's device arrays without
        materializing them: the call enqueues on XLA's async dispatch
        stream and returns immediately, so the caller can overlap
        host-side work (coalescing the next batch) with device execution.
        Convert with np.asarray to block until the values are ready —
        device errors also surface there."""
        feed_vals = self._prepare_feed(inputs)
        self._note_signature(feed_vals)
        return self._jitted(self._weights, feed_vals)

    def cost_analysis(self, inputs):
        """XLA `cost_analysis()` for the executable serving this feed
        signature (flops / bytes_accessed per execution, as the compiled
        HLO reports them — after fusion, after int8 rewrite).  Cached
        per signature; `lower().compile()` reuses the already-built
        executable after warmup.  Returns None when the backend reports
        nothing (attribution is telemetry, never an error source)."""
        feed_vals = self._prepare_feed(inputs)
        from ..observability.xla_cost import cost_of_jitted, feed_signature

        sig = feed_signature(feed_vals)
        if sig in self._costs:
            return self._costs[sig]

        cost = cost_of_jitted(self._jitted, self._weights, feed_vals)
        if cost is not None:      # don't let one transient failure
            self._costs[sig] = cost   # disable attribution forever
        return cost

    def warmup(self, bucket_specs):
        """AOT-compile the executables for a set of feed signatures before
        traffic arrives (server-start warmup over the bucket ladder).

        bucket_specs: iterable of {feed_name: spec} dicts where spec is a
        shape tuple (float32 assumed), a (shape, dtype) pair, or a
        ready-made array.  Blocks until every executable is built;
        returns the resulting compile_count.

        To warm a MEASURED-tuned ladder instead of the default one,
        feed this the specs of a tuned `BatchingConfig`
        (`cfg.ladder_specs(example)` with
        `batch_buckets=tune.search_bucket_ladder(...)` winner buckets)
        — or use `InferenceServer.autotune`, which searches, adopts,
        and warms in one call.
        """
        import jax

        for spec in bucket_specs:
            feed = {}
            for name, s in spec.items():
                if isinstance(s, np.ndarray):
                    feed[name] = s
                elif (isinstance(s, (tuple, list)) and len(s) == 2
                        and not isinstance(s[1], (int, np.integer))):
                    feed[name] = np.zeros(tuple(s[0]), np.dtype(s[1]))
                else:
                    feed[name] = np.zeros(tuple(s), np.float32)
            jax.block_until_ready(self.run_async(feed))
        return self.compile_count


def create_predictor(config: AnalysisConfig) -> Predictor:
    """cf. reference CreatePaddlePredictor / create_predictor."""
    return Predictor(config)


# ---------------------------------------------------------------------------
# Portable StableHLO export (serving without Python)
# ---------------------------------------------------------------------------


def export_stablehlo(dirname, predictor: Predictor, example_inputs):
    """Serialize the predictor's computation via jax.export: weights are
    baked as constants closed over by the exported function (the analogue
    of the reference's frozen __model__ + params single artifact)."""
    import jax
    from jax import export as jexport

    if isinstance(example_inputs, dict):
        feed_vals = {k: np.asarray(v) for k, v in example_inputs.items()}
    else:
        feed_vals = {
            n: np.asarray(v)
            for n, v in zip(predictor._feed_names, example_inputs)
        }

    weights = predictor._weights

    def serving_fn(feed_vals):
        return predictor._jitted(weights, feed_vals)

    exported = jexport.export(jax.jit(serving_fn))(feed_vals)
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "model.stablehlo"), "wb") as f:
        f.write(exported.serialize())
    return exported


def load_stablehlo(dirname):
    """Deserialize + call: returns fn(feed_vals_dict) -> [outputs]."""
    from jax import export as jexport

    with open(os.path.join(dirname, "model.stablehlo"), "rb") as f:
        exported = jexport.deserialize(f.read())
    return exported.call
