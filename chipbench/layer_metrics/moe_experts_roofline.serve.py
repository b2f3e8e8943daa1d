"""The experts' products against their roofline, which memory bandwidth
bounds at a step's 4 x slots rows (8 / 128 of 128 rows visit an expert:
8 FLOPs a byte of its weights): the bytes they must move
(`moe_cost.experts_bytes`: the visited experts' weights and the rows in
and out) over the HBM peak, over their device time a step
(`moe_experts_ms_per_pass.serve`)."""

from chipbench import moe_cost
from chipbench.scope_trace import scope_ms_per_execution


def read(obs):
    ms = scope_ms_per_execution(obs, "moe_experts", "generation_block_step")
    means = moe_cost.step_means(obs)
    if not ms or not means or not obs.get("peaks"):
        return None
    touched, _, live = means
    config = obs["config"]
    need = moe_cost.experts_bytes(
        config, touched, live * config["block_length"])
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
