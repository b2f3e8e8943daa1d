#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of the models the repo has numbers for, and checks what
comes out by the repo's own means:

* kernels: each Pallas kernel of the main path against its jnp oracle;
* train:   BERT-base pretraining (12 layers, hidden 768, 12 heads, FFN
           3072, vocab 30528, S=512, P=80, B=48) through
           `models.BertForPretraining` + `AdamWOptimizer` +
           `dist.ShardedTrainStep(zero_stage=0, amp="bf16")`;
* static:  `fluid.Program` + `layers.fc` + `AdamOptimizer.minimize` +
           `fluid.Executor(TPUPlace(0))`, with an `Assert` op in the
           program (a host callback from inside the compiled step);
* serve:   the GPT-2-small-shaped `TransformerLM` (12 layers, 768, 12
           heads, vocab 32000, 1024 positions) behind
           `serving.GenerationFleet` + `serving.serve_generation_http`,
           `POST /generate` answered over HTTP; greedy streams compared
           token for token with the model's plain full forward.

`--four-chips` runs the multi-chip path and what it is compared with, and
no other phase: ZeRO-2 BERT-base on four chips against ZeRO-0 on one, and
TP=4 decode against the one-chip engine.

One process, the only one that touches JAX.  It fails (non-zero exit, no
final line) the moment the platform is not "tpu", a phase raises, a loss
is not finite, or a compiled step that should hold a kernel has no
`tpu_custom_call`.  The last line of stdout, and nothing else on it, is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Every reading printed on the earlier lines (compile seconds, step wall
time, peak bytes) is a SMOKE READING on the named device: one cold run,
no warm-up discipline, no repeats.  It is not a benchmark number.
"""

import argparse
import contextlib
import http.client
import json
import re
import sys
import threading
import time

import numpy as np

LOGPROB_ATOL = 2e-3      # float32 paths that differ in summation order
                         # agree to ~1e-4; bfloat16 products would miss
                         # by 1e-2 or more
KERNEL_ATOL = 2e-4       # kernel vs jnp oracle, float32 operands
ZERO2_LOSS_RTOL = 1e-2   # bf16 step, gradient sums taken in another order
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def say(phase, **fields):
    """One earlier line: ``[phase] key=value ...`` (values as JSON)."""
    print("[%s] %s" % (phase, " ".join(
        "%s=%s" % (k, json.dumps(v, default=str)) for k, v in fields.items())),
        flush=True)


def kernel_calls(hlo_text):
    """(forward, backward) counts of Pallas custom calls in optimized
    HLO: a call that autodiff transposed carries ``transpose(`` in its
    op_name metadata."""
    fwd = bwd = 0
    for line in hlo_text.splitlines():
        if CUSTOM_CALL in line:
            m = re.search(r'op_name="([^"]*)"', line)
            if m and "transpose(" in m.group(1):
                bwd += 1
            else:
                fwd += 1
    return fwd, bwd


def dispatch_choices():
    from paddle_tpu.ops import dispatch

    return dispatch.choices()


def dispatch_delta(before):
    """Kernel dispatch decisions traced since ``before``."""
    out = []
    for key, n in sorted(dispatch_choices().items()):
        d = n - before.get(key, 0)
        if d:
            out.append("%s -> %s (%s) x%d" % (key + (d,)))
    return out


def need(cond, msg):
    if not cond:
        raise AssertionError(msg)


@contextlib.contextmanager
def float32_products():
    """Multiply float32 operands in float32, on every thread (the
    engine traces on its own): at the TPU's default precision a float32
    matmul rounds its operands to bfloat16, and with random weights the
    largest logit changes on that rounding — greedy tokens could then
    not be compared one by one, nor a kernel with its oracle."""
    import jax

    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", old)


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel of the main path against its jnp oracle
# ---------------------------------------------------------------------------


def phase_kernels(ctx):
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import naive_attention_with_layout
    from paddle_tpu.ops.pallas.attention import flash_attention

    tiny, interp = ctx["tiny"], (None if ctx["on_tpu"] else True)
    rng = np.random.RandomState(ctx["seed"])
    h, d = (2, 64) if tiny else (12, 64)
    s = 128 if tiny else 256

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    fq, fk, fv = rand(2, s, h, d), rand(2, s, h, d), rand(2, s, h, d)

    checks = {
        "flash_attention causal": (
            lambda: flash_attention(fq, fk, fv, causal=True, layout="BSHD",
                                    interpret=interp),
            lambda: naive_attention_with_layout(fq, fk, fv, None, d ** -0.5,
                                                True, "BSHD")),
    }
    for name, (kernel, oracle) in checks.items():
        with float32_products():
            got, want = np.asarray(kernel()), np.asarray(oracle())
        err = float(np.max(np.abs(got - want)))
        say("kernels", kernel=name, shape=list(got.shape), max_abs_err=err,
            atol=KERNEL_ATOL)
        need(np.isfinite(got).all() and err <= KERNEL_ATOL,
             "%s differs from its oracle by %g" % (name, err))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def bert_config(tiny, dropout):
    from paddle_tpu import models

    if tiny:
        return models.BertConfig.tiny(), (8, 32, 8)
    cfg = models.BertConfig(    # BERT-base, as bench.py builds it
        vocab_size=30528,       # padded to a multiple of 64 for the lanes
        hidden_size=768, num_hidden_layers=12, num_attention_heads=12,
        intermediate_size=3072, max_position_embeddings=512,
        hidden_dropout_prob=dropout, attention_probs_dropout_prob=dropout)
    return cfg, (48, 512, 80)   # B as in the last driver capture


def bert_batch(cfg, b, s, p, rng):
    return {
        "input_ids": rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int32),
        "token_type_ids": np.zeros((b, s), np.int32),
        "position_ids": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
        "masked_positions": np.stack([
            np.sort(rng.choice(s, size=p, replace=False))
            for _ in range(b)]).astype(np.int32),
        "mlm_labels": rng.randint(0, cfg.vocab_size, (b, p)).astype(np.int32),
        "mlm_weights": np.ones((b, p), np.float32),
        "nsp_labels": rng.randint(0, 2, (b, 1)).astype(np.int32),
    }


def bert_loss(m, batch):
    logits, nsp_logits = m(
        batch["input_ids"], batch["token_type_ids"], batch["position_ids"],
        masked_positions=batch["masked_positions"])
    return m.loss(logits, nsp_logits, batch["mlm_labels"],
                  batch["mlm_weights"], batch["nsp_labels"])


def run_bert(ctx, phase, cfg, batches, *, mesh, zero_stage, steps):
    """Build the sharded step on ``mesh``, take ``steps`` steps cycling
    over ``batches``, return (losses, step object, final state, a batch)."""
    import jax

    from paddle_tpu import distributed as dist
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.optimizer import AdamWOptimizer

    with dygraph.guard():
        np.random.seed(ctx["seed"])
        model = models.BertForPretraining(cfg)
        opt = AdamWOptimizer(learning_rate=1e-4, weight_decay=0.01)
        step = dist.ShardedTrainStep(model, opt, bert_loss, mesh,
                                     zero_stage=zero_stage, amp="bf16")
        state = step.init()
        placed = [step.place_batch(b) for b in batches]
        losses, walls = [], []
        for i in range(steps):
            t0 = time.perf_counter()
            state, loss = step(state, placed[i % len(placed)])
            jax.block_until_ready(loss)
            walls.append(time.perf_counter() - t0)
            losses.append(float(loss))      # fetched to the host each step
            need(np.isfinite(losses[-1]),
                 "%s: loss %r at step %d" % (phase, losses[-1], i))
    say(phase, zero_stage=zero_stage, devices=mesh.size,
        first_call_s_incl_compile=round(walls[0], 2),
        step_wall_ms_smoke=[round(w * 1e3, 1) for w in walls[1:]],
        losses=[round(x, 4) for x in losses])
    return losses, step, state, placed[0]


def phase_train(ctx):
    from paddle_tpu import distributed as dist

    cfg, (b, s, p) = bert_config(ctx["tiny"], dropout=0.1)
    batch = bert_batch(cfg, b, s, p, np.random.RandomState(ctx["seed"]))
    before = dispatch_choices()
    losses, step, state, placed = run_bert(
        ctx, "train", cfg, [batch], mesh=dist.auto_mesh(1), zero_stage=0,
        steps=3 if ctx["tiny"] else 6)
    need(losses[-1] < losses[0],
         "train: loss did not fall on a repeated batch: %r" % (losses,))
    param_dev = next(iter(next(iter(state["params"].values())).devices()))
    fwd, bwd = kernel_calls(step.compiled_hlo(state, placed))
    dev = ctx["device"]
    stats = dev.memory_stats() or {}
    say("train", batch=[b, s, p], param_device=str(param_dev),
        flash_fwd_custom_calls=fwd, flash_bwd_custom_calls=bwd,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        dispatch=dispatch_delta(before))
    need(param_dev == dev,
         "train: parameters live on %s, not %s" % (param_dev, dev))
    if ctx["on_tpu"]:
        need(fwd >= cfg.num_hidden_layers and bwd >= cfg.num_hidden_layers,
             "train: compiled step holds %d forward / %d backward "
             "flash-attention custom calls for %d layers"
             % (fwd, bwd, cfg.num_hidden_layers))


# ---------------------------------------------------------------------------
# static
# ---------------------------------------------------------------------------


def phase_static(ctx):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.optimizer import AdamOptimizer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = ctx["seed"] + 1
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        label = layers.data("y", shape=[1], dtype="int64")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(x, 10), label))
        # a host callback from inside the compiled step: it either works
        # on this backend or the run fails here
        layers.control_flow.Assert(layers.less_than(
            loss, layers.fill_constant([1], "float32", 1e6)),
            data=[loss], message="chip_smoke static loss")
        AdamOptimizer(1e-2).minimize(loss)
    rng = np.random.RandomState(ctx["seed"])
    xs = rng.randn(64, 4).astype(np.float32)
    ys = (xs[:, :1] > 0).astype(np.int64)
    place = fluid.TPUPlace(0) if ctx["on_tpu"] else fluid.CPUPlace()
    exe = fluid.Executor(place)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        losses, devices = [], set()
        for _ in range(8):
            out, = exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss],
                           return_numpy=False)
            devices |= set(out.devices())
            losses.append(float(np.asarray(out).reshape(-1)[0]))
    say("static", place=repr(place), fetch_devices=sorted(map(str, devices)),
        losses=[round(v, 4) for v in losses])
    need(np.isfinite(losses).all() and losses[-1] < losses[0],
         "static: loss did not fall: %r" % (losses,))
    need(devices == {ctx["device"]},
         "static: fetches were produced on %s, not %s"
         % (devices, ctx["device"]))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def lm_model(ctx):
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph

    if ctx["tiny"]:
        cfg = models.TransformerLMConfig.tiny()
    else:                       # GPT-2-small shape (generation_bench.py)
        cfg = models.TransformerLMConfig(
            vocab_size=32000, hidden_size=768, num_layers=12, num_heads=12,
            intermediate_size=3072, max_position_embeddings=1024,
            dropout=0.0)
    with dygraph.guard():
        np.random.seed(ctx["seed"] + 7)
        return cfg, models.TransformerLM(cfg)


def lm_requests(ctx, cfg):
    """Greedy and sampled prompts whose lengths land in two prefill
    buckets; the long one makes prefill take the causal flash kernel."""
    rng = np.random.RandomState(ctx["seed"] + 11)
    long_len = 40 if ctx["tiny"] else 200
    new = 4 if ctx["tiny"] else 8
    reqs = []
    for i, (plen, temp) in enumerate(
            [(5, 0.0), (8, 0.0), (long_len, 0.0), (7, 0.8), (3, 0.8)]):
        reqs.append({
            "request_id": "smoke-%d" % i,
            "prompt": [int(t) for t in rng.randint(0, cfg.vocab_size, plen)],
            "max_new_tokens": new, "temperature": temp,
            "top_k": 40 if temp else 0, "top_p": 0.95 if temp else 1.0,
            "seed": 1000 + i, "stream": True, "timeout": 900.0})
    return reqs


def plain_forward_generate(model, cfg, reqs, pad_to):
    """The reference: for every greedy request, one full causal forward
    over the whole sequence per generated token — no cache, no engine.
    The sequence is right-padded to a fixed length (causal attention
    keeps padding out of earlier rows) so the forward compiles once.
    Returns {request_id: (tokens, logprobs)}."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid import dygraph, framework

    params = {k: v.data for k, v in model.state_dict().items()}

    @jax.jit
    def logits_at(params, ids, last):
        with dygraph.guard():
            tracer = framework._dygraph_tracer
            tracer.train_mode = tracer._has_grad = False
            for var in model.state_dict().values():
                tracer.register_var(var)
            pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
            out = model.functional_call(
                params, dygraph.to_variable(ids), dygraph.to_variable(pos))
        row = jax.lax.dynamic_index_in_dim(out.data[0], last, 0, False)
        return jax.nn.log_softmax(row.astype(jnp.float32))

    out = {}
    for r in reqs:
        if r["temperature"]:
            continue
        seq, toks, lps = list(r["prompt"]), [], []
        for _ in range(r["max_new_tokens"]):
            ids = np.zeros((1, pad_to), np.int32)
            ids[0, :len(seq)] = seq
            lp = np.asarray(logits_at(params, ids, len(seq) - 1))
            tok = int(np.argmax(lp))
            toks.append(tok)
            lps.append(float(lp[tok]))
            seq.append(tok)
        out[r["request_id"]] = (toks, lps)
    return out


def post_generate(port, body, results):
    """Client side of one request: POST /generate, read the ndjson
    stream to its terminal record."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=1000)
    try:
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        records = [json.loads(line) for line in resp if line.strip()]
        results[body["request_id"]] = (resp.status, records)
    finally:
        conn.close()


def serve_once(ctx, label, model, cfg, reqs, want, **engine_kwargs):
    """One engine configuration behind the fleet and the HTTP front;
    every request sent at once from client threads, answers checked."""
    from paddle_tpu import serving

    before = dispatch_choices()
    max_len = 64 if ctx["tiny"] else 1024
    fleet = serving.GenerationFleet(
        model, replicas=1, slots=4, max_len=max_len, logprobs=True,
        name="smoke-" + label, **engine_kwargs).start()
    server = serving.serve_generation_http(fleet, port=0, block=False)
    port = server.server_address[1]
    results, t0 = {}, time.perf_counter()
    try:
        clients = [threading.Thread(target=post_generate,
                                    args=(port, r, results)) for r in reqs]
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=1100)
        need(not any(c.is_alive() for c in clients),
             "serve/%s: a client is still waiting" % label)
        wall = time.perf_counter() - t0
        engine = fleet.replicas[0].engine
        stats = engine.stats()
        decode_fwd, _ = kernel_calls(engine.decode_hlo())
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop()

    worst = 0.0
    for r in reqs:
        rid = r["request_id"]
        need(rid in results, "serve/%s: %s got no answer" % (label, rid))
        status, records = results[rid]
        done = records[-1] if records else {}
        need(status == 200 and done.get("done") and "error" not in done,
             "serve/%s: %s answered %s %r" % (label, rid, status, done))
        toks = [x["token"] for x in records if "token" in x]
        lps = [x["logprob"] for x in records if "token" in x]
        need(len(toks) == r["max_new_tokens"]
             and all(0 <= t < cfg.vocab_size for t in toks)
             and np.isfinite(lps).all(),
             "serve/%s: %s stream %r %r" % (label, rid, toks, lps))
        if rid in want:
            ref_toks, ref_lps = want[rid]
            need(toks == ref_toks,
                 "serve/%s: %s greedy tokens %r differ from the plain "
                 "forward's %r" % (label, rid, toks, ref_toks))
            worst = max(worst, float(np.max(np.abs(
                np.asarray(lps) - np.asarray(ref_lps)))))
    need(worst <= LOGPROB_ATOL,
         "serve/%s: token logprobs differ from the plain forward's by %g"
         % (label, worst))
    say("serve", engine=label, answered=len(results),
        greedy_equal_to_plain_forward=len(want),
        max_logprob_diff=worst, logprob_atol=LOGPROB_ATOL,
        executables=stats["executables"],
        decode_step_custom_calls=decode_fwd,
        wall_s_incl_compile_smoke=round(wall, 2),
        dispatch=dispatch_delta(before))
    return decode_fwd


def phase_serve(ctx):
    cfg, model = lm_model(ctx)
    reqs = lm_requests(ctx, cfg)
    with float32_products():
        want = plain_forward_generate(
            model, cfg, reqs, pad_to=64 if ctx["tiny"] else 256)
        buckets = [8, 64] if ctx["tiny"] else [8, 256]
        common = dict(model=model, cfg=cfg, reqs=reqs, want=want,
                      prefill_buckets=buckets)
        big = 32 if ctx["tiny"] else 128    # one block a chunk of the walk
        calls = {
            "paged-bs%d" % big: serve_once(
                ctx, "paged-bs%d" % big, paged=True, block_size=big,
                **common),
            "dense": serve_once(ctx, "dense", paged=False, **common),
            "paged-default-bs16": serve_once(
                ctx, "paged-default-bs16", **common),
        }
    if ctx["on_tpu"]:
        need(not any(calls.values()),
             "serve: a decode step reached a kernel (custom calls %r): "
             "update this check" % (calls,))
        print("[serve] NOTE the engine holds its cache with the heads "
              "merged ([.., H*D]) and every decode step walks its live "
              "part as it lies (cached_attention): 0 custom calls.  No "
              "decode kernel exists; ROADMAP S1 is one that streams the "
              "merged form", flush=True)


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------


def phase_zero2(ctx):
    """ZeRO-2 on four chips against ZeRO-0 on one, same batches.  Dropout
    is off here: ZeRO-2 draws each rank's mask from its own key, so with
    dropout on the two trajectories differ by design."""
    import jax

    from paddle_tpu import distributed as dist

    cfg, (b, s, p) = bert_config(ctx["tiny"], dropout=0.0)
    rng = np.random.RandomState(ctx["seed"])
    batches = [bert_batch(cfg, b, s, p, rng) for _ in range(2)]
    steps = 6
    one, _, state, _ = run_bert(
        ctx, "zero0-one-chip", cfg, batches, zero_stage=0, steps=steps,
        mesh=dist.auto_mesh(1, devices=jax.devices()[:1]))
    del state
    four, step, state, placed = run_bert(
        ctx, "zero2-four-chips", cfg, batches, zero_stage=2, steps=steps,
        mesh=dist.auto_mesh(4, devices=jax.devices()[:4]))
    dev = float(np.max(np.abs(np.asarray(four) - np.asarray(one))
                       / np.abs(np.asarray(one))))
    stats = step.collective_stats(state, placed)
    counts = {k: stats.get(k, {}).get("count", 0)
              for k in ("reduce-scatter", "all-gather", "all-reduce")}
    fwd, bwd = kernel_calls(step.compiled_hlo(state, placed))
    # optimizer state: a quarter of its bytes on each device, not all of
    # them on the first (the replicated rest is Adam's beta-power scalars)
    total = on_first = 0
    holders = set()
    for slots in state["opt"].values():
        for arr in slots.values():
            total += arr.nbytes
            for sh in arr.addressable_shards:
                holders.add(sh.device)
                if sh.device == jax.devices()[0]:
                    on_first += sh.data.nbytes
    say("zero2", max_rel_loss_diff=dev, rtol=ZERO2_LOSS_RTOL,
        collectives=counts, flash_fwd_custom_calls=fwd,
        flash_bwd_custom_calls=bwd, opt_state_bytes=total,
        opt_state_bytes_on_first_device=on_first,
        opt_state_devices=len(holders))
    need(dev <= ZERO2_LOSS_RTOL, "zero2: loss trajectory %r is not within "
         "%g of one chip's %r" % (four, ZERO2_LOSS_RTOL, one))
    need(min(four[1:]) < four[0], "zero2: loss did not fall: %r" % (four,))
    need(counts["all-gather"] > 0, "zero2: no all-gather in the step")
    need(counts["reduce-scatter"] + counts["all-reduce"] > 0,
         "zero2: no gradient collective in the step")
    if not counts["reduce-scatter"]:
        print("[zero2] FINDING the gradient sync compiled to all-reduce, not "
              "reduce-scatter: this compiler decomposes the step's 1-D "
              "psum_scatter (ROADMAP S5)", flush=True)
    need(len(holders) == 4 and on_first <= 0.3 * total,
         "zero2: optimizer state is not spread over four devices: %d of "
         "%d bytes on the first of %d" % (on_first, total, len(holders)))


def phase_tp4(ctx):
    from paddle_tpu import generation as gen
    from paddle_tpu import serving, tp_serving

    cfg, model = lm_model(ctx)
    reqs = [r for r in lm_requests(ctx, cfg) if not r["temperature"]]
    max_len = 64 if ctx["tiny"] else 1024
    kw = dict(slots=4, max_len=max_len, paged=True,
              block_size=32 if ctx["tiny"] else 128,
              prefill_buckets=[8, 64] if ctx["tiny"] else [8, 256])

    def run(label, **extra):
        fleet = serving.GenerationFleet(model, replicas=1,
                                        name="smoke-" + label, **kw,
                                        **extra).start()
        try:
            handles = [fleet.submit(gen.GenerationRequest(
                np.asarray(r["prompt"], np.int64),
                max_new_tokens=r["max_new_tokens"],
                request_id=r["request_id"] + label)) for r in reqs]
            toks = [h.result(timeout=900) for h in handles]
            return toks, fleet.replicas[0].engine
        finally:
            fleet.stop()

    with float32_products():
        one, _ = run("one-chip")
        four, engine = run("tp4", engine_cls=tp_serving.TPGenerationEngine,
                           tp=4)
        chk = engine.decode_hlo_comm_check()
        decode_fwd, _ = kernel_calls(engine.decode_hlo())
    say("tp4", requests=len(reqs), tokens_equal_to_one_chip=(one == four),
        all_reduces_in_decode_step=chk["hlo_all_reduce_count"],
        expected=chk["all_reduce_count"], wire_match=chk["wire_match"],
        decode_step_custom_calls=decode_fwd,
        devices=engine.stats()["tp"]["devices"])
    need(one == four, "tp4: greedy tokens %r differ from one chip's %r"
         % (four, one))
    need(chk["hlo_all_reduce_count"] > 0 and chk["count_match"],
         "tp4: decode step all-reduces %r" % (chk,))
    if ctx["on_tpu"]:
        need(decode_fwd == 0, "tp4: the decode step reached a kernel (%d "
             "custom calls): update this check" % decode_fwd)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def main(tiny=False, require_tpu=True, four_chips=False, seed=0):
    """Run the phases; returns the final record.  ``tiny`` and
    ``require_tpu=False`` exist for `tests/test_chip_smoke.py`, which
    rehearses the phases on the CPU; the command line has neither."""
    import jax

    from paddle_tpu.fluid.core.compile_cache import enable_compile_cache
    from paddle_tpu.observability import (
        default_registry,
        install_jax_compile_hooks,
    )
    from paddle_tpu.observability.xla_cost import chip_peaks

    install_jax_compile_hooks()
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    # the CPU rehearsal keeps no cache: XLA:CPU entries are tied to the
    # host's instruction set and are not what a chip run would reuse
    cache_dir = enable_compile_cache() if on_tpu else None
    if require_tpu and not on_tpu:
        raise RuntimeError("chip_smoke needs a TPU: jax.devices()[0] is %r "
                           "(platform %r)" % (dev, dev.platform))
    want = 4 if four_chips else 1
    if len(devices) < want:
        raise RuntimeError("chip_smoke%s needs %d device(s), jax has %d"
                           % (" --four-chips" if four_chips else "", want,
                              len(devices)))
    # asked once so that a chip the peaks table does not know fails here
    peaks = chip_peaks(dev.device_kind)
    say("start", platform=dev.platform, device_kind=dev.device_kind,
        devices=len(devices), jax=jax.__version__, tiny=tiny, seed=seed,
        compile_cache_dir=cache_dir, peaks=peaks)

    ctx = {"tiny": tiny, "on_tpu": on_tpu, "seed": seed, "device": dev}
    phases = ([phase_zero2, phase_tp4] if four_chips else
              [phase_kernels, phase_train, phase_static, phase_serve])
    def count(name):
        fam = default_registry().get(name)
        return int(fam.value) if fam is not None else 0

    for phase in phases:
        t0 = time.perf_counter()
        phase(ctx)              # no phase's exception is caught
        say(phase.__name__, ok=True,
            seconds=round(time.perf_counter() - t0, 1),
            xla_compilations_so_far=count("xla_compilations_total"),
            compile_cache_hits_so_far=count("xla_compile_cache_hits_total"),
            compile_cache_writes_so_far=count(
                "xla_compile_cache_misses_total"))
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(devices)}}


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path and what it is "
                         "compared with (needs four chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args()
    record = main(four_chips=args.four_chips, seed=args.seed)
    print(json.dumps(record))
    sys.exit(0)
