"""The program's own spans in a `jax.profiler` session, and the counters
at its layer boundaries: a tiny engine served over HTTP, a chunk-prefill
engine and a few `ShardedTrainStep` calls behind a `DevicePrefetcher`, all
inside one CPU session that nothing told the program about.  Every span
name of the two hot paths has to be on a host plane, nested as stated,
with one ``trace_id`` per request across the handler and loop threads, and
every new histogram observed as often as the script says.  Nothing here
asserts a time."""

import gc
import glob
import http.client
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu  # noqa: E402
from chipbench import program_trace  # noqa: E402
from paddle_tpu import distributed as dist  # noqa: E402
from paddle_tpu import io, models  # noqa: E402
from paddle_tpu.fluid import dygraph  # noqa: E402
from paddle_tpu.fluid.optimizer import AdamWOptimizer  # noqa: E402
from paddle_tpu.observability import default_registry  # noqa: E402
from paddle_tpu.observability import trace as T  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402

gen = paddle_tpu.generation
serving = paddle_tpu.serving

REQUESTS, NEW_TOKENS, TRAIN_STEPS = 3, 4, 3
LM = models.TransformerLMConfig.tiny()


def post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, [json.loads(x) for x in resp if x.strip()]
    finally:
        conn.close()


def bert_loss(model, batch):
    logits, nsp = model(batch["input_ids"], batch["token_type_ids"],
                        batch["position_ids"])
    return model.loss(logits, nsp, batch["mlm_labels"],
                      batch["mlm_weights"], batch["nsp_labels"])


def bert_batch(cfg, rng, b=8, s=16):
    return {
        "input_ids": rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int64),
        "token_type_ids": np.zeros((b, s), np.int64),
        "position_ids": np.tile(np.arange(s, dtype=np.int64), (b, 1)),
        "mlm_labels": rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int64),
        "mlm_weights": np.ones((b, s), np.float32),
        "nsp_labels": rng.randint(0, 2, (b, 1)).astype(np.int64),
    }


def count(snap, name):
    return sum(s.get("count") or 0 for s in snap[name]["series"])


@pytest.fixture(scope="module")
def scripted(tmp_path_factory):
    """Run the script once; ``{"spans", "served", "train", "decode_steps",
    "ring"}``."""
    assert not T.tracing_enabled()      # the ring is off: only the session
    rng = np.random.RandomState(3)
    reg = MetricsRegistry()
    with dygraph.guard():
        np.random.seed(0)
        lm = models.TransformerLM(LM)
        bert_cfg = models.BertConfig.tiny()
        bert = models.BertForPretraining(bert_cfg)
        step = dist.ShardedTrainStep(
            bert, AdamWOptimizer(learning_rate=1e-3), bert_loss,
            dist.auto_mesh(1, devices=jax.devices()[:1]), zero_stage=0)
        state = step.init()
        batches = [bert_batch(bert_cfg, rng) for _ in range(2)]
        state, loss = step(state, batches[0])           # compiles
        float(loss)

        fleet = serving.GenerationFleet(
            lm, replicas=1, name="spans", metrics_registry=reg, slots=2,
            max_len=64, prefill_buckets=[8, 16]).start()
        server = serving.serve_generation_http(fleet, port=0, block=False)
        port = server.server_address[1]
        engine = fleet.replicas[0].engine
        chunked = gen.GenerationEngine(
            lm, slots=2, max_len=64, prefill_buckets=[8, 16],
            prefill_chunk=4, metrics_registry=MetricsRegistry())
        try:
            for plen in (5, 12):                        # both buckets compile
                status, _ = post(port, {"prompt": list(range(1, plen + 1)),
                                        "max_new_tokens": 2})
                assert status == 200
            chunked.generate([list(range(1, 10))], max_new_tokens=2)
            for fam in reg.collect() + default_registry().collect():
                if fam.type == "histogram":
                    fam.clear()
            steps_before = engine.stats()["decode_steps"]

            trace_dir = str(tmp_path_factory.mktemp("program_spans"))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                served = []
                for i in range(REQUESTS):
                    status, records = post(port, {
                        "request_id": "r%d" % i, "stream": True,
                        "prompt": [int(t) for t in rng.randint(
                            1, LM.vocab_size, 5 + 4 * i)],
                        "max_new_tokens": NEW_TOKENS})
                    assert status == 200 and records[-1].get("done")
                    served.append(sum("token" in r for r in records))
                time.sleep(0.12)                # the loop idles: idle_wait
                gc.collect()                    # a collection: generation.gc
                chunked.generate([list(range(1, 10))], max_new_tokens=2)
                feed = iter(io.DevicePrefetcher(iter(batches * 2), depth=2))
                for _ in range(TRAIN_STEPS):
                    state, loss = step(state, next(feed))
                float(loss)
                feed.close()
            finally:
                jax.profiler.stop_trace()
            decode_steps = engine.stats()["decode_steps"] - steps_before
            served_snap, train_snap = reg.snapshot(), \
                default_registry().snapshot()
        finally:
            server.shutdown()
            server.server_close()
            fleet.stop()
    path = max(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return {"spans": program_trace.load(path)["spans"], "tokens": served,
            "served": served_snap, "train": train_snap,
            "decode_steps": decode_steps,
            "ring": len(T.default_tracer())}


SPAN_NAMES = [
    "http.generate", "http.admit", "generation.step", "generation.lock_wait",
    "generation.admit", "generation.prefill", "generation.prefill_chunk",
    "generation.grow", "generation.decode_dispatch",
    "generation.dispatch_merge", "generation.dispatch_operands",
    "generation.dispatch_call", "generation.gc",
    "generation.decode_fetch", "generation.emit", "generation.idle_wait",
    "train.step_dispatch", "train.batch_put", "io.next_batch"]


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_span_is_in_the_session_without_the_ring(scripted, name):
    assert any(s.name == name for s in scripted["spans"]), sorted(
        {s.name for s in scripted["spans"]})
    assert scripted["ring"] == 0        # enable_tracing() was never called


NESTING = [
    ("http.admit", "http.generate"),
    ("generation.lock_wait", "generation.step"),
    ("generation.admit", "generation.step"),
    ("generation.prefill", "generation.admit"),
    ("generation.prefill_chunk", "generation.step"),
    ("generation.grow", "generation.step"),
    ("generation.decode_dispatch", "generation.step"),
    ("generation.dispatch_merge", "generation.decode_dispatch"),
    ("generation.dispatch_operands", "generation.decode_dispatch"),
    ("generation.dispatch_call", "generation.decode_dispatch"),
    ("generation.decode_fetch", "generation.step"),
    ("generation.emit", "generation.step"),
    ("train.batch_put", "train.step_dispatch"),
]


@pytest.mark.parametrize("child,parent", NESTING)
def test_span_nests_in_its_parent_on_the_same_thread(scripted, child,
                                                     parent):
    spans = scripted["spans"]
    children = [s for s in spans if s.name == child]
    assert children
    for c in children:
        assert any(p.name == parent and p.thread == c.thread
                   and p.start <= c.start and c.end <= p.end
                   for p in spans), (c, parent)


def test_idle_wait_and_step_do_not_overlap(scripted):
    steps = [s for s in scripted["spans"] if s.name == "generation.step"]
    for w in (s for s in scripted["spans"]
              if s.name == "generation.idle_wait"):
        assert not any(s.thread == w.thread and s.start < w.end
                       and w.start < s.end for s in steps)


@pytest.mark.parametrize("i", range(REQUESTS))
def test_one_trace_id_per_request_across_handler_and_loop(scripted, i):
    rid = "r%d" % i
    mine = [s for s in scripted["spans"] if s.args.get("request_id") == rid]
    names = {s.name for s in mine}
    assert {"http.generate", "http.admit", "generation.admit",
            "generation.prefill"} <= names
    ids = {s.args.get("trace_id") for s in mine}
    assert len(ids) == 1 and None not in ids
    handler = {s.thread for s in mine if s.name.startswith("http.")}
    loop = {s.thread for s in mine if s.name.startswith("generation.")}
    assert len(handler) == 1 and len(loop) == 1 and handler != loop
    # another request has another id
    others = {s.args.get("trace_id") for s in scripted["spans"]
              if s.name == "http.generate"
              and s.args.get("request_id") != rid}
    assert not (ids & others)


HISTOGRAMS = [      # family, registry, observations the script makes
    ("generation_front_admit_ms", "served", lambda r: REQUESTS),
    ("generation_queue_wait_ms", "served", lambda r: REQUESTS),
    ("generation_stream_lag_ms", "served", lambda r: sum(r["tokens"])),
    ("generation_sched_host_ms", "served", lambda r: r["decode_steps"]),
    ("generation_itl_ms", "served", lambda r: r["decode_steps"]),
    ("generation_attn_walk_share", "served", lambda r: r["decode_steps"]),
    ("generation_sampling_step_share", "served",
     lambda r: r["decode_steps"]),
    ("generation_prefill_ms", "served", lambda r: REQUESTS),
    ("generation_dispatch_ms", "served", lambda r: r["decode_steps"]),
    ("generation_dispatch_call_ms", "served", lambda r: r["decode_steps"]),
    ("train_step_dispatch_ms", "train", lambda r: TRAIN_STEPS),
    ("io_step_wait_ms", "train", lambda r: TRAIN_STEPS),
]


@pytest.mark.parametrize("family,registry,expected", HISTOGRAMS,
                         ids=[h[0] for h in HISTOGRAMS])
def test_histogram_is_observed_as_often_as_the_script_says(
        scripted, family, registry, expected):
    assert scripted["tokens"] == [NEW_TOKENS] * REQUESTS
    assert scripted["decode_steps"] >= NEW_TOKENS - 1
    assert count(scripted[registry], family) == expected(scripted)


def test_decode_dispatch_spans_carry_the_walk_share(scripted):
    """Each decode step's `generation.decode_dispatch` span says what
    part of the cache its attention walks: the value the step gave
    `generation_attn_walk_share`."""
    # the served engine's loop thread (the chunk-prefill engine decodes
    # on the test's own thread, into a registry of its own)
    loop = {s.thread for s in scripted["spans"]
            if s.name == "generation.idle_wait"}
    shares = [float(s.args["attn_walk_share"]) for s in scripted["spans"]
              if s.name == "generation.decode_dispatch" and s.thread in loop]
    assert len(shares) == scripted["decode_steps"]
    assert all(0.0 < x <= 1.0 for x in shares)
    series = scripted["served"]["generation_attn_walk_share"]["series"]
    assert sum(s["sum"] for s in series) == pytest.approx(sum(shares))
    # and whether a live row sampled: the script's requests are greedy
    assert [float(s.args["sampling_step_share"]) for s in scripted["spans"]
            if s.name == "generation.decode_dispatch"
            and s.thread in loop] == [0.0] * len(shares)


def test_a_step_function_is_known_by_its_name():
    with dygraph.guard():
        np.random.seed(0)
        engine = gen.GenerationEngine(
            models.TransformerLM(LM), slots=2, max_len=64,
            prefill_buckets=[8, 16], prefill_chunk=4,
            metrics_registry=MetricsRegistry())
        engine.generate([list(range(1, 10))], max_new_tokens=2)
    names = {"decode": engine._decode_step_fn.__name__,
             **{b: f.__name__ for b, f in engine._prefill_fns.items()},
             **{"c%d" % w: f.__name__
                for w, f in engine._chunk_fns.items()}}
    assert names == {"decode": "generation_decode",
                     8: "generation_prefill_8", 16: "generation_prefill_16",
                     "c4": "generation_prefill_chunk_4"}
    assert "HloModule jit_generation_decode" in engine.decode_hlo()
