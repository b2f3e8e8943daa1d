"""`chipbench.program_trace` on hand-made intervals and event names (no
profiler, no chip), and the per-layer metrics that read it or the
program's counters (twelve of PR 26, four of PR 34): each
resolves to its reader, reads its number where the program gives one, and
returns None, without raising, where the program gives none (the parent
of the PR that brought them)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import program_trace as pt  # noqa: E402
from chipbench import run  # noqa: E402

# event names and op_names as a v5e trace of the BERT step has them
FWD = ('%flash_attention_fwd.12 = (bf16[576,512,64]{2,1,0:T(8,128)(2,1)S(1)}, '
       'f32[576,8,128]{2,1,0:T(8,128)}) custom-call(bf16[576,512,64]{2,1,0} '
       '%bitcast.1624), custom_call_target="tpu_custom_call", '
       'frontend_attributes={kernel_metadata={}}')
BWD = ('%flash_attention_bwd_fused.3 = (bf16[576,512,64]{2,1,0}) '
       'custom-call(%a), custom_call_target="tpu_custom_call"')
ADAM = ('%multiply_subtract_fusion.140 = (f32[768]{0:T(1024)}, '
        'f32[768]{0:T(1024)}) fusion(%p, %g), kind=kLoop')
FFN = '%fusion.406 = bf16[48,512,3072]{2,1,0} fusion(%x), kind=kOutput'
# a weight-gradient matmul with the weight's AdamW update fused into it
MM_ADAM = ('%multiply_subtract_fusion.37 = (f32[768,768]{1,0:T(8,128)}, '
           'f32[768,768]{1,0}, f32[768,768]{1,0}) fusion(%w, %g), '
           'kind=kOutput')
OTHER = ('%all_reduce_ish.1 = f32[8]{0} custom-call(%x), '
         'custom_call_target="SomethingElse"')
PATHS = {
    FWD: "jit(train_step)/loss_and_grad/jvp(flash_attention)/"
         "flash_attention_fwd/pallas_call:",
    BWD: "jit(train_step)/loss_and_grad/transpose(loss_and_grad)/"
         "jvp(flash_attention)/flash_attention_bwd_fused/pallas_call:",
    ADAM: "jit(train_step)/optimizer_update/sub:",
    FFN: "jit(train_step)/loss_and_grad/jvp()/dot_general:",
    MM_ADAM: "jit(train_step)/loss_and_grad/transpose(jvp())/dot_general:",
}
ROOTS = {   # a fusion's event name -> the op_name of each output of its root
    MM_ADAM: ["jit(train_step)/optimizer_update/sub:",
              "jit(train_step)/optimizer_update/add:",
              "jit(train_step)/optimizer_update/add:"],
    FFN: ["jit(train_step)/loss_and_grad/jvp()/dot_general:", ""],
}


@pytest.mark.parametrize("event,want", [
    ("jit_generation_decode(7204012)", "generation_decode"),
    ("jit_generation_prefill_128(991)", "generation_prefill_128"),
    ("jit_generation_prefill_chunk_32(5)", "generation_prefill_chunk_32"),
    ("jit_train_step(31)", "train_step"),
    ("jit_train_step", "train_step"),
    ("generation_verify", "generation_verify"),
])
def test_module_name_strips_the_jit_prefix_and_the_fingerprint(event, want):
    assert pt.module_name(event) == want


@pytest.mark.parametrize("event,want", [
    (FWD, "flash_attention_fwd"), (BWD, "flash_attention_bwd_fused"),
    (ADAM, None), (OTHER, None), ("not an instruction", None),
])
def test_kernel_name_is_the_name_the_pallas_call_was_given(event, want):
    assert pt.kernel_name(event) == want


@pytest.mark.parametrize("path,want", [
    (PATHS[ADAM], "optimizer_update"), (PATHS[FFN], "loss_and_grad"),
    (PATHS[FWD], "flash_attention"), (PATHS[BWD], "flash_attention"),
    ("jit(train_step)/transpose(jvp(loss_and_grad))/mul", "loss_and_grad"),
    ("jit(train_step)/loss_and_grad/flash_attention/x", "flash_attention"),
    ("jit(train_step)/flash_attention_fwd/pallas_call", None),
    ("jit(train_step)/my_optimizer_update/sub", None),
    ("", None),
])
def test_scope_of_is_the_innermost_named_scope_on_the_path(path, want):
    assert pt.scope_of(path) == want


@pytest.mark.parametrize("own,root,want", [
    (PATHS[MM_ADAM], ROOTS[MM_ADAM], "optimizer_update"),
    (PATHS[FFN], ["jit(train_step)/loss_and_grad/reduce_sum", ""],
     "loss_and_grad"),
    (PATHS[FFN], [""], "loss_and_grad"),         # a convert XLA added
    (PATHS[FFN], (), "loss_and_grad"),           # not a fusion
    ("", [PATHS[ADAM]], "optimizer_update"),
    (PATHS[FFN], [PATHS[ADAM], PATHS[FFN]], "loss_and_grad"),   # no one scope
    ("", [""], None),
])
def test_a_fusion_counts_under_the_one_scope_its_root_names(own, root, want):
    assert pt.fusion_scope(own, root) == want


# An XSpace serialized by the protobuf library (tensorflow's xplane_pb2
# and hlo_pb2).  A device plane whose event metadata are a matmul's output
# fusion whose root is the tuple of an update's ``sub`` and ``add``
# (stats: tf_op as a string, hlo_category as a reference to a stat
# metadata's name, program_id over 2**63, a number), a fusion whose root
# is a ``convert`` without op_name, and a ``copy`` without stats; the
# ``/host:metadata`` plane with the program's HloProto under the
# program_id's signed twin; an empty host plane.
TINY_XSPACE = bytes.fromhex(
    "0afa030803120d2f6465766963653a5450553a301a1708011207584c41204f70"
    "73220a0807100a188088debe0122b201080712ad010807124e256d756c746970"
    "6c795f73756274726163745f667573696f6e2e31203d20286633325b385d2c20"
    "6633325b385d2920667573696f6e286633325b385d202570292c206b696e643d"
    "6b4f75747075742a3e08012a3a6a697428747261696e5f73746570292f6c6f73"
    "735f616e645f677261642f7472616e73706f7365286a76702829292f646f745f"
    "67656e6572616c2a04080238032a0d080418df8bbae7b8c4d3bfb8012a040809"
    "20052228080912240809122025636f70792e33203d206633325b385d20636f70"
    "79286633325b385d20257929228a0108081285010808123125667573696f6e2e"
    "32203d20626631365b385d20667573696f6e286633325b385d202578292c206b"
    "696e643d6b4c6f6f702a3308012a2f6a697428747261696e5f73746570292f6c"
    "6f73735f616e645f677261642f6a767028292f646f745f67656e6572616c2a04"
    "080238032a0d080418df8bbae7b8c4d3bfb8012a04080920052a0d0809120908"
    "091205666c6f70732a120804120e0804120a70726f6772616d5f69642a1a0803"
    "121608031212636f6e766f6c7574696f6e20667573696f6e2a14080212100802"
    "120c686c6f5f63617465676f72792a0d080112090801120574665f6f700ae805"
    "0804120e2f686f73743a6d6574616461746122c00508df8bbae7b8c4d3bfb801"
    "12b20508df8bbae7b8c4d3bfb80112246a69745f747261696e5f737465702831"
    "33323934343330353339363136323537353033292afe04080132f9040af6040a"
    "0e6a69745f747261696e5f737465701aad020a1366757365645f636f6d707574"
    "6174696f6e2e3112170a07706172616d2e301209706172616d6574657298020a"
    "12590a05646f742e31120b636f6e766f6c7574696f6e3a3c123a6a6974287472"
    "61696e5f73746570292f6c6f73735f616e645f677261642f7472616e73706f73"
    "65286a76702829292f646f745f67656e6572616c98020ba202010a12460a0a73"
    "756274726163742e31120873756274726163743a2612246a697428747261696e"
    "5f73746570292f6f7074696d697a65725f7570646174652f73756298020ca202"
    "020a0b123c0a056164642e3112036164643a2612246a697428747261696e5f73"
    "746570292f6f7074696d697a65725f7570646174652f61646498020da202020a"
    "0b12180a077475706c652e3112057475706c6598020ea202020c0d2801300e1a"
    "4f0a1366757365645f636f6d7075746174696f6e2e3212170a07706172616d2e"
    "311209706172616d65746572980214121b0a09636f6e766572742e391207636f"
    "6e76657274980215a2020114280230151ae0010a066d61696e2e3312110a0170"
    "1209706172616d6574657298021e126d0a1a6d756c7469706c795f7375627472"
    "6163745f667573696f6e2e311206667573696f6e3a3c123a6a69742874726169"
    "6e5f73746570292f6c6f73735f616e645f677261642f7472616e73706f736528"
    "6a76702829292f646f745f67656e6572616c98021fa202011eb202010112500a"
    "08667573696f6e2e321206667573696f6e3a31122f6a697428747261696e5f73"
    "746570292f6c6f73735f616e645f677261642f6a767028292f646f745f67656e"
    "6572616c980220a202011fb20201022803302030032a110801120d0801120948"
    "6c6f2050726f746f0a0b12092f686f73743a435055")
MM_EVENT = ("%multiply_subtract_fusion.1 = (f32[8], f32[8]) "
            "fusion(f32[8] %p), kind=kOutput")
FFN_EVENT = "%fusion.2 = bf16[8] fusion(f32[8] %x), kind=kLoop"


def test_op_names_reads_what_profile_data_does_not_show(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(TINY_XSPACE)
    paths, roots = pt.op_names(str(path))
    assert paths == {
        MM_EVENT: "jit(train_step)/loss_and_grad/transpose(jvp())/"
                  "dot_general",
        FFN_EVENT: "jit(train_step)/loss_and_grad/jvp()/dot_general"}
    assert roots == {
        MM_EVENT: ["jit(train_step)/optimizer_update/sub",
                   "jit(train_step)/optimizer_update/add"],
        FFN_EVENT: [""]}
    scopes = {ev: pt.fusion_scope(paths[ev], roots[ev]) for ev in paths}
    assert scopes == {MM_EVENT: "optimizer_update",
                      FFN_EVENT: "loss_and_grad"}


def spans(*rows):
    return [pt.Span(name, s, e, thread, {}) for name, s, e, thread in rows]


def test_idle_goes_to_the_innermost_span_of_a_driving_thread():
    device = [("decode", 10, 20), ("decode", 30, 40), ("prefill", 50, 55),
              ("decode", 80, 90)]
    host = spans(
        ("http.generate", 0, 100, "h"),         # names no idle
        ("generation.step", 5, 27, "loop"),
        ("generation.emit", 21, 25, "loop"),
        ("generation.step", 28, 58, "loop"),
        ("generation.admit", 41, 56, "loop"),
        ("generation.prefill", 48, 56, "loop"),
        ("generation.idle_wait", 60, 75, "loop"))
    idle = pt.idle_by_span(device, host)
    # gaps: 20-30, 40-50, 55-80
    assert idle == {
        "generation.step": (21 - 20) + (27 - 25) + (30 - 28) + (41 - 40)
        + (58 - 56),
        "generation.emit": 4, "generation.admit": 48 - 41,
        "generation.prefill": (50 - 48) + (56 - 55),
        "generation.idle_wait": 15,
        pt.UNATTRIBUTED: (28 - 27) + (60 - 58) + (80 - 75)}
    assert sum(idle.values()) == 10 + 10 + 25
    named = 45 - 15 - 8
    assert pt.attributed_share(idle) == pytest.approx(100.0 * named / 45)
    assert pt.attributed_share({}) is None
    assert pt.idle_by_span([], host) == {}


def test_a_busy_device_has_no_idle_to_name():
    assert pt.idle_by_span([("a", 0, 5), ("b", 5, 9)], spans(
        ("train.step_dispatch", 0, 9, "main"))) == {}


def test_executions_split_the_programs_by_name():
    modules = [("jit_generation_decode(1)", 0, 90),
               ("jit_generation_prefill_128(2)", 90, 130),
               ("jit_generation_decode(1)", 130, 221),
               ("jit_generation_prefill_512(3)", 221, 300),
               ("jit_generation_draft_decode(4)", 300, 301)]
    assert pt.executions(modules) == {
        "generation_decode": [90, 91], "generation_prefill_128": [40],
        "generation_prefill_512": [79], "generation_draft_decode": [1]}


def test_self_seconds_count_a_kernel_inside_a_loop_once():
    ops = [("%while.1 = () while(%t)", 0, 100), (FWD, 10, 30), (BWD, 30, 70),
           (ADAM, 110, 120), (FWD, 120, 125)]
    assert pt.grouped(pt.self_times(ops), pt.kernel_name) == {
        "flash_attention_fwd": 25, "flash_attention_bwd_fused": 40}


def hand_made_trace():
    ms = 1e6
    return {
        "spans": spans(("train.step_dispatch", 0, 3 * ms, "main"),
                       ("bench.loss_fetch", 95 * ms, 101 * ms, "main"),
                       ("train.step_dispatch", 101 * ms, 104 * ms, "main")),
        "modules": {0: [("jit_train_step(9)", 0, 95 * ms),
                        ("jit_train_step(9)", 100 * ms, 200 * ms)]},
        "ops": {0: [(FWD, 0, 10 * ms), (FFN, 10 * ms, 40 * ms),
                    (MM_ADAM, 40 * ms, 50 * ms),
                    (BWD, 50 * ms, 70 * ms), (ADAM, 70 * ms, 95 * ms),
                    (FWD, 100 * ms, 110 * ms), (FFN, 110 * ms, 140 * ms),
                    (MM_ADAM, 140 * ms, 150 * ms),
                    (BWD, 150 * ms, 175 * ms), (ADAM, 175 * ms, 200 * ms)]},
        "paths": PATHS, "roots": ROOTS,
    }


def test_summarize_gives_window_idle_programs_kernels_and_scopes():
    red = pt.summarize(hand_made_trace())
    assert red["devices"] == 1 and red["window_s"] == pytest.approx(0.2)
    assert red["idle_s"] == {"bench.loss_fetch": pytest.approx(0.005)}
    assert red["span_seconds"]["train.step_dispatch"] == (
        2, pytest.approx(0.006))
    assert red["module_ms"] == {"train_step": [95.0, 100.0]}
    assert red["kernel_s"] == {
        "flash_attention_fwd": pytest.approx(0.020),
        "flash_attention_bwd_fused": pytest.approx(0.045)}
    assert red["scope_s"] == {
        "flash_attention": pytest.approx(0.065),
        "loss_and_grad": pytest.approx(0.060),
        "optimizer_update": pytest.approx(0.070)}


NEW = {     # metric -> (cell, what a full observation reads)
    "front_admit_ms_p95.serve": ("gpt2-medium-chat-steady", 9.0),
    "stream_lag_ms_p95.serve": ("gpt2-medium-chat-steady", 0.4),
    "queue_wait_ms_p95.serve": ("gpt2-medium-chat-steady", 120.0),
    "sched_host_ms_p50.serve": ("gpt2-medium-chat-steady", 5.0),
    "sched_host_ms_max.serve": ("gpt2-medium-chat-steady", 41.0),
    "decode_device_ms_p50.serve": ("gpt2-medium-chat-steady", 86.0),
    "prefill_device_ms_p50.serve": ("gpt2-medium-chat-steady", 38.0),
    "idle_attributed_share.serve": ("gpt2-medium-chat-steady", 80.0),
    "dispatch_ms_p50.train": ("bert-base-pretrain-1chip", 2.5),
    "flash_attn_fwd_ms_per_step.train": ("bert-base-pretrain-1chip", 18.0),
    "flash_attn_bwd_ms_per_step.train": ("bert-base-pretrain-1chip", 40.5),
    "optimizer_update_ms_per_step.train": ("bert-base-pretrain-1chip",
                                           63.0),
    # PR 34: the engine's two step shares, and the collectives of a step
    "attn_walk_share.serve": ("gpt2-medium-chat-steady", 9.0),
    "sampling_step_share.serve": ("gpt2-medium-chat-steady", 60.0),
    "collective_ms_per_step.train": ("bert-base-pretrain-zero2-4chip", 18.0),
    "collective_exposed_share.train": ("bert-base-pretrain-zero2-4chip",
                                       2.0),
}


def histogram_series(**stats):
    return {"type": "histogram", "series": [dict(
        labels={"engine": "e"}, count=10, **stats)]}


@pytest.fixture
def full_obs(monkeypatch):
    """An observation in which every new reader finds its number."""
    red = pt.summarize(hand_made_trace())
    red["module_ms"].update(generation_decode=[85.0, 86.0, 90.0],
                            generation_prefill_128=[30.0],
                            generation_prefill_256=[38.0, 44.0])
    red["idle_s"] = {"generation.step": 0.5, "generation.emit": 0.3,
                     "generation.idle_wait": 0.1, pt.UNATTRIBUTED: 0.1}
    red["span_seconds"]["generation.step"] = (30, 2.9)
    assert red["collective_s"] == {}        # one chip: no collective
    red["collective_s"] = {"in_flight": 0.02, "exposed": 0.004}
    monkeypatch.setattr(pt, "_newest_session", lambda: red)
    return {
        "trace": {"window_s": 0.2, "busy_s": 0.19},
        "samples": {"step_ms": [180.0, 180.0, 180.0]},
        "counters_after": {
            "generation_front_admit_ms": histogram_series(p95=9.0),
            "generation_stream_lag_ms": histogram_series(p95=0.4),
            "generation_queue_wait_ms": histogram_series(p95=120.0),
            "generation_sched_host_ms": histogram_series(p50=5.0, max=41.0),
            "train_step_dispatch_ms": histogram_series(p50=2.5),
            "generation_attn_walk_share": histogram_series(sum=0.9),
            "generation_sampling_step_share": histogram_series(sum=6.0),
        }}


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_resolves_and_reads_or_keeps_silent(
        name, full_obs, monkeypatch):
    bench = run.load_json(REPO, "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    cell, want = NEW[name]
    assert cell in entry["workloads"]
    assert entry in run.metrics_of(bench, "per_layer", cell)
    read = run.load_reader(REPO, bench, "per_layer", name)
    assert read(full_obs) == pytest.approx(want)
    # an untraced run, and a program without the span, name or counter
    # (the parent): nothing, and no raise
    assert read({"trace": None, "counters_after": {},
                 "samples": {"step_ms": [1.0]}}) is None
    monkeypatch.setattr(pt, "_newest_session", lambda: {
        "window_s": 0.2, "idle_s": {pt.UNATTRIBUTED: 0.2}, "module_ms": {
            "decode": [91.0], "step": [181.0]}, "kernel_s": {},
        "scope_s": {}, "collective_s": {},
        "span_seconds": {"bench.step": (10, 0.05)}})
    assert read(dict(full_obs, counters_after={})) is None


W1, W2 = "bert-base-pretrain-1chip", "gpt2-medium-chat-steady"
PR25 = [    # name, unit, better, source, layer, moves, its first cell
    ("device_idle_share.train", "%", "lower", "device_trace", "device",
     "train_tokens_per_s", W1),
    ("step_ms_p50.train", "ms", "lower", "host_clock", "train step",
     "train_tokens_per_s", W1),
    ("model_flops_util.train", "%", "higher", "host_clock", "train step",
     "train_tokens_per_s", W1),
    ("data_wait_ms_p50.train", "ms", "lower", "program_counter",
     "input pipeline", "train_tokens_per_s", W1),
    ("window_compilations.train", "count", "lower", "program_counter",
     "step functions", "train_tokens_per_s", W1),
    ("device_idle_share.serve", "%", "lower", "device_trace", "device",
     "itl_ms_p95", W2),
    ("window_compilations.serve", "count", "lower", "program_counter",
     "step functions", "latency_ms_per_token", W2),
    ("decode_step_ms_p50.serve", "ms", "lower", "program_counter",
     "step functions", "itl_ms_p95", W2),
    ("slot_occupancy_mean.serve", "%", "lower", "program_counter",
     "scheduler", "latency_ms_per_token", W2),
    ("kv_pool_live_share.serve", "%", "higher", "program_counter",
     "scheduler", "latency_ms_per_token", W2),
    ("prefill_ms_p50.serve", "ms", "lower", "program_counter", "scheduler",
     "itl_ms_p95", W2),
    ("shed_share.serve", "%", "lower", "host_clock", "HTTP front",
     "latency_ms_per_token", W2),
    ("gen_lateness_ms_p95.serve", "ms", "lower", "host_clock",
     "load generator", "latency_ms_per_token", W2),
    ("flash_attn_ms_per_step.train", "ms", "lower", "device_trace",
     "kernels", "train_tokens_per_s", W1),
    ("flash_attn_roofline.train", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s", W1),
    ("ttft_ms_p95.serve", "ms", "lower", "host_clock", "HTTP front",
     "latency_ms_per_token", W2),
    ("ttft_ms_p50.serve", "ms", "lower", "host_clock", "HTTP front",
     "latency_ms_per_token", W2),
    ("tokens_per_s.serve", "tokens/s", "higher", "host_clock", "HTTP front",
     "latency_ms_per_token", W2),
]


def test_the_entries_this_pr_found_are_first_and_as_they_were():
    """A later PR appends metrics, and cells to a metric's ``workloads``;
    it changes nothing else of what was there."""
    per_layer = run.load_json(REPO, "BENCHMARK.json")["per_layer"]
    keys = ("name", "unit", "better", "source", "layer", "moves")
    assert [tuple(m[k] for k in keys) + (m["workloads"][0],)
            for m in per_layer[:len(PR25)]] == PR25
    assert set(NEW) <= {m["name"] for m in per_layer}


def test_session_is_none_for_an_untraced_run_and_off_the_chip():
    assert pt.session({"trace": None}) is None
    assert pt.session({}) is None
