"""`compile_serve_for_chip` for a serving cell whose engine generates by
block diffusion (the configuration's ``block_length``): compile the
engine's block step and its prefill chunk at the cell's real sizes for a
*described* v5e chip, here, without the chip.

    JAX_PLATFORMS=cpu python3 -m chipbench.tools.compile_block_serve_for_chip \
        --config chipbench/configs/sdar-30b-a3b-serve.json [--slots N] [--layers N]

It raises what the chip's compiler would raise and prints each program's
memory analysis.  Nothing runs.  The engine builds its real weights and
pool on the CPU (10 GB and some minutes for six published layers)."""

import argparse
import importlib
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu import generation

    with open(args.config) as f:
        config = json.load(f)
    if args.layers:
        config["num_hidden_layers"] = args.layers
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
    jax.default_backend = lambda: "tpu"

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                           if not hasattr(a, "dtype")
                                           else a.dtype, sharding=chip),
            tree)

    w = engine.prefill_chunk
    programs = {
        "block-step": (engine._decode_step_fn, engine._decode_operands()),
        "prefill-chunk-%d" % w: (
            jax.jit(engine._make_block_chunk_fn(w),
                    donate_argnums=engine._donate_kv),
            (engine._params, *engine.cache.arrays(),
             np.zeros((1, w), np.int32), np.int32(0),
             engine.cache.table_row(0)[None].astype(np.int32))),
    }
    for name, (fn, operands) in programs.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*shapes(operands)).compile()
        print("[%s] slots=%d layers=%d compiled in %.1f s (host time, not "
              "a chip reading)\n  %s" % (
                  name, engine.slots, config["num_hidden_layers"],
                  time.perf_counter() - t0, compiled.memory_analysis()),
              flush=True)


if __name__ == "__main__":
    main()
