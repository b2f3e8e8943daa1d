"""The whole block step against its roofline, which memory bandwidth
bounds: the bytes a step must move (`moe_cost.block_step_bytes`: the
weights of the experts its live rows visit, from the engine's count of
them, every layer's dense weights, the head, the live cache rows; the
window's mean a step) over the chip's HBM peak, over the median device
time of `generation_block_step`."""

from chipbench import moe_cost
from chipbench.program_trace import module_ms_p50


def read(obs):
    ms = module_ms_p50(obs, "generation_block_step")
    means = moe_cost.step_means(obs)
    if not ms or not means or not obs.get("peaks"):
        return None
    touched, cache_rows, live = means
    config = obs["config"]
    need = moe_cost.block_step_bytes(
        config, touched, cache_rows, live * config["block_length"])
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
