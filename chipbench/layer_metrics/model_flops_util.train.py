"""Model FLOP/s utilization: closed-form FLOPs of a step (chipbench/flops.py)
times steps per second of the window (less what the profiler's own start
and stop took of a traced one), over chips times the bf16 peak."""


def read(obs):
    if not obs.get("peaks"):
        return None             # no chip, no peak: not a CPU number
    rate = obs["flops_per_step"] * obs["steps"] / (
        obs["window_s"] - obs["profiler_s"])
    return 100.0 * rate / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
