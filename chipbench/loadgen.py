"""The load generator: a process of its own that imports no JAX and no
numpy (the parent holds the chip and runs the server; a generator inside
it would fight the engine's scheduler thread for the interpreter lock).

    python3 -m chipbench.loadgen --schedule <in.json> --out <out.json>

``in.json``: ``{"port", "start_at", "give_up_at", "requests": [{"due",
"body"}]}`` with times on ``time.monotonic()``, which is one clock for
every process of the machine.  Each request is sent at ``start_at + due``
whatever the server is doing (open loop), streamed, and every line's
arrival is stamped.  ``out.json``: one record a request."""

import argparse
import http.client
import json
import sys
import threading
import time


def one_request(port, item, start_at, give_up_at, records):
    rec = {"id": item["body"]["request_id"], "due": start_at + item["due"],
           "send": time.monotonic(), "status": None, "lines": [],
           "stamps": [], "error": None, "end": None}
    records.append(rec)
    payload = json.dumps(item["body"])
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=max(1.0, give_up_at - time.monotonic()))
    try:
        conn.request("POST", "/generate", payload,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        for line in resp:
            rec["stamps"].append(time.monotonic())
            rec["lines"].append(line)
    except Exception as e:          # recorded: a failed request, not a crash
        rec["error"] = "%s: %s" % (type(e).__name__, e)
    finally:
        rec["end"] = time.monotonic()
        conn.close()


def parse(rec):
    """Stamps and values of the token lines, after the run."""
    tokens, logprobs, stamps, done, err = [], [], [], False, rec["error"]
    for t, line in zip(rec["stamps"], rec["lines"]):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if "token" in obj:
            tokens.append(obj["token"])
            logprobs.append(obj.get("logprob"))
            stamps.append(t)
        elif obj.get("done"):
            done = "error" not in obj
            err = err or obj.get("error")
        elif obj.get("shed") or "error" in obj:
            err = err or obj.get("error")
    return {"id": rec["id"], "due": rec["due"], "send": rec["send"],
            "end": rec["end"], "status": rec["status"], "error": err,
            "done": done, "tokens": tokens, "logprobs": logprobs,
            "token_times": stamps}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.schedule) as f:
        plan = json.load(f)
    start_at, give_up_at = plan["start_at"], plan["give_up_at"]
    records, threads = [], []
    for item in sorted(plan["requests"], key=lambda r: r["due"]):
        wait = start_at + item["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=one_request, daemon=True, args=(
            plan["port"], item, start_at, give_up_at, records))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=max(0.0, give_up_at - time.monotonic()))
    out = [parse(r) for r in list(records)]
    with open(args.out, "w") as f:
        json.dump({"records": out,
                   "unfinished": sum(t.is_alive() for t in threads)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
