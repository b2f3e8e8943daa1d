"""Run one cell of BENCHMARK.json once, as a new process:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Earlier lines of standard output describe the run; the last line is the
one JSON object the contract fixes.  The command line has no tiny or CPU
switch: off the chip the run fails and prints no result.  (Tests call
`run_cell` with ``require_chip=False`` and a benchmark file of their own.)
"""

import time

_T0 = time.perf_counter()       # set-up is counted from here

import argparse                 # noqa: E402
import importlib                # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_cell(root, workload, benchmark="BENCHMARK.json"):
    """The cell's entry, its configuration and its traffic, each found by
    the name BENCHMARK.json gives it."""
    bench = load_json(root, benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in %s (has: %s)"
                         % (workload, benchmark, sorted(cells)))
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root, entry["file"])
    traffic = load_json(root, find(root, bench, "traffic",
                                   cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def find(root, bench, *parts):
    """A file of the benchmark by its place under one of ``paths``: a
    later PR's directory is searched like the first one's."""
    for base in bench["paths"]:
        path = os.path.join(base, *parts)
        if os.path.exists(os.path.join(root, path)):
            return path
    raise FileNotFoundError("no %s under %s" % (os.path.join(*parts),
                                                bench["paths"]))


def metrics_of(bench, group, workload):
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_reader(root, bench, group, name):
    """The metric's own reader, a function ``read(obs)``, in
    ``<paths>/end_to_end/<name>.py`` or ``<paths>/layer_metrics/<name>.py``.
    A name with a suffix (``x.train``) that has no file of its own takes
    ``x.py``: one reader serves a quantity that is split over two names
    only because its cells report different end-to-end metrics."""
    stems = [name] + ([name.rsplit(".", 1)[0]] if "." in name else [])
    for stem in stems:
        try:
            path = find(root, bench, READERS[group], stem + ".py")
            break
        except FileNotFoundError:
            if stem == stems[-1]:
                raise
    path = os.path.join(root, path)
    spec = importlib.util.spec_from_file_location(
        "chipbench_reader_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def attach_trace(record, red, *, on_chip, workload, kind):
    """Put the reduced trace of a traced run into its record:
    ``device.busy_s``, ``device.window_s`` and ``breakdown``.  On the chip
    a line without them is refused as malformed and says nothing of why,
    so a kind that hands back no reduced trace raises here with the
    reason.  (The CPU rehearsal's session has no device plane; its line
    goes without them.)"""
    if not red:
        if on_chip:
            raise RuntimeError(
                "the traced run of %s has no device.busy_s and "
                "device.window_s to report: kind %r handed back no reduced "
                "trace (obs['trace'] = %r), so no profiler session ran in "
                "the window or it holds no operation on a device plane; "
                "the line [trace] above lists the planes it found"
                % (workload, kind, red))
        return
    record["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
    record["breakdown"] = {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}


def run_cell(workload, seed, seconds, trace, *, require_chip=True,
             root=ROOT, benchmark="BENCHMARK.json", groups=None):
    """Run the cell and return the result record (the last line).
    ``groups`` names the metric groups to read, by default the one the
    contract gives ``--trace``; `tools/sweep_rate.py` asks for both of an
    untraced run, whose trace readers then find nothing and say nothing."""
    import jax

    from chipbench import common, peaks
    from paddle_tpu.fluid.core.compile_cache import enable_compile_cache
    from paddle_tpu.observability import install_jax_compile_hooks

    bench, cell, config, traffic = load_cell(root, workload, benchmark)
    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise SystemExit("chipbench needs a TPU: jax.devices()[0] is %r "
                         "(platform %r)" % (dev, dev.platform))
    if len(devices) < cell["chips"]:
        raise SystemExit("workload %s needs %d chip(s), jax has %d"
                         % (workload, cell["chips"], len(devices)))
    chip = peaks.chip_peaks(dev.device_kind) if require_chip else None
    # the CPU rehearsal keeps no cache: XLA:CPU entries are tied to the
    # host's instruction set and are not what a chip run would reuse
    cache_dir = enable_compile_cache() if dev.platform == "tpu" else None
    # every program goes to the cache, the many small ones of model
    # set-up too (JAX keeps only those that took a second by default)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    install_jax_compile_hooks()
    common.say("start", workload=workload, seed=seed, seconds=seconds,
               trace=trace, platform=dev.platform, kind=dev.device_kind,
               devices=len(devices), chips=cell["chips"],
               jax=jax.__version__, compile_cache_dir=cache_dir)

    marks = common.Marks(_T0)
    marks("imports-and-devices")
    runner = importlib.import_module(
        "chipbench.kinds." + traffic["kind"]).run
    obs = runner({
        "t0": _T0, "seed": int(seed), "seconds": float(seconds),
        "trace": bool(trace), "cell": cell, "config": config,
        "traffic": traffic, "devices": devices[:cell["chips"]],
        "peaks": chip, "root": root, "mark": marks,
        "trace_dir": os.path.join(root, ".chipbench_trace", workload),
    })

    metrics = {}
    for group in groups or (["per_layer"] if trace else ["end_to_end"]):
        for m in metrics_of(bench, group, workload):
            value = load_reader(root, bench, group, m["name"])(obs)
            # a per-layer reader that finds nothing leaves its metric out;
            # an end-to-end metric has to be there
            if value is None and group == "end_to_end":
                raise RuntimeError("the run gave no %s" % m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

    for d in devices[:cell["chips"]]:
        common.say("memory", device=str(d), stats=d.memory_stats())
    device = common.device_record(devices, cell["chips"])
    # a runner that computes a reference on the device after its window
    # reads the peak before it: a process's peak never falls again
    device["memory_peak_bytes"] = int(obs.get(
        "memory_peak_bytes", device["memory_peak_bytes"]))
    record = {"correct": bool(obs["correct"]),
              "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        attach_trace(record, obs.get("trace"), on_chip=require_chip,
                     workload=workload, kind=traffic["kind"])
    if cache_dir:
        size = sum(os.path.getsize(os.path.join(cache_dir, f))
                   for f in os.listdir(cache_dir)
                   if os.path.isfile(os.path.join(cache_dir, f)))
        snap = obs["counters_after"]
        common.say("cache", dir=cache_dir, bytes=size,
                   hits=common.counter_value(
                       snap, "xla_compile_cache_hits_total"),
                   writes=common.counter_value(
                       snap, "xla_compile_cache_misses_total"),
                   compilations=common.counter_value(
                       snap, "xla_compilations_total"))
    # each number compared beside its limit: the line's last key, and the
    # last lines of standard error
    record["checks"] = obs["checks"]
    for name, c in record["checks"].items():
        print("[compared] %s = %r (limit %r)" % (name, c["value"],
                                                 c["limit"]),
              file=sys.stderr, flush=True)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    record = run_cell(args.workload, args.seed, args.seconds, args.trace)
    sys.stdout.flush()
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
