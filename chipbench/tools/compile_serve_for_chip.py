"""Rehearsal 3 of the on-chip-measurement guide for a serving cell: compile
the engine's decode step and its largest reachable prefill step at the
cell's real sizes for a *described* v5e chip, here, without the chip.

    JAX_PLATFORMS=cpu python3 -m chipbench.tools.compile_serve_for_chip \
        [--config chipbench/configs/gpt2-medium-serve.json] [--bucket 512] [--slots N]

It raises what the chip's compiler would raise (a kernel refused, a program
that does not fit the device) and prints each program's memory analysis.
Nothing runs: a compile that passes is not a chip run.  It takes minutes
and about 12 GB of host memory (the engine builds its real pool on the
CPU), which is why it is a script and not a test."""

import argparse
import importlib
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config",
                    default="chipbench/configs/gpt2-medium-serve.json")
    ap.add_argument("--bucket", type=int, default=512)
    ap.add_argument("--slots", type=int, default=None,
                    help="try another number of slots than the file's")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu import generation

    with open(args.config) as f:
        config = json.load(f)
    builder = importlib.import_module(config["builder"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)

    model = builder.build(config, 0)
    kw = {k: v for k, v in config["serving"].items() if k != "replicas"}
    if args.slots:
        kw["slots"] = args.slots
    engine = generation.GenerationEngine(model, donate=True, **kw)
    # dispatches ask jax.default_backend(): make them take the chip's branch
    jax.default_backend = lambda: "tpu"

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                           if not hasattr(a, "dtype")
                                           else a.dtype, sharding=chip),
            tree)

    n, b = engine.slots, args.bucket
    key = np.zeros(2, np.uint32)
    programs = {
        "decode": (engine._decode_step_fn, (
            engine._params, *engine.cache.arrays(), engine._lengths,
            engine._last_tokens, engine._keys, engine._steps, engine._temp,
            engine._top_k, engine._top_p, engine._decode_tables())),
        "prefill-%d" % b: (engine._prefill_fns[b], (
            engine._params, *engine.cache.arrays(),
            np.zeros((1, b), np.int32), np.int32(b),
            engine.cache.table_row(0)[None].astype(np.int32), key,
            np.float32(0.0), np.int32(0), np.float32(1.0))),
    }
    for name, (fn, operands) in programs.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*shapes(operands)).compile()
        text = compiled.as_text()
        print("[%s] slots=%d compiled in %.1f s (host time, not a chip "
              "reading); tpu_custom_calls=%d\n  %s" % (
                  name, n, time.perf_counter() - t0,
                  text.count('custom_call_target="tpu_custom_call"'),
                  compiled.memory_analysis()), flush=True)


if __name__ == "__main__":
    main()
