"""Find a serving cell's knee once: run the cell at each of a few offered
rates, one new process a rate (a chip belongs to one process at a time;
this parent never touches JAX), and print one line a rate.

    python3 -m chipbench.tools.sweep_rate --workload gpt2-medium-chat-steady \
        --rates 2,3,4,5,6 --seconds 30 --seed 1

Read the knee from the lines: the highest swept rate with no shed or failed
request at which requests do not yet queue for a slot (TTFT p95 and
latency_ms_per_token stay near their values at the lower rates).  The tool
picks nothing: the cell's traffic file gets 0.8 x the knee as a number, by
hand, and PERF.md the sweep.  No run of a cell imports this file."""

import argparse
import json
import subprocess
import sys


def one(args, rate):
    code = (
        "import json, sys\n"
        "from chipbench import run\n"
        "load = run.load_cell\n"
        "def at_rate(*a, **k):\n"
        "    bench, cell, config, traffic = load(*a, **k)\n"
        "    return bench, cell, config, dict(traffic, rate_per_s=%r)\n"
        "run.load_cell = at_rate\n"
        "rec = run.run_cell(%r, %d, %r, 0,\n"
        "                   groups=['end_to_end', 'per_layer'])\n"
        "print(json.dumps(rec))\n" % (rate, args.workload, args.seed,
                                      args.seconds))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True)
    lines = proc.stdout.strip().splitlines()
    window = [ln for ln in lines if ln.startswith("[window]")]
    row = {"rate_per_s": rate, "rc": proc.returncode,
           "window": window[-1] if window else None}
    if proc.returncode == 0:
        rec = json.loads(lines[-1])
        row.update(correct=rec["correct"], attempted=rec["attempted"],
                   failed=rec["failed"], checks=rec["checks"],
                   **{k: v["value"] for k, v in rec["metrics"].items()})
    else:
        row["stderr"] = proc.stderr[-2000:]
    print(json.dumps(row), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        one(args, rate)


if __name__ == "__main__":
    main()
