"""Device time one block step spends in the experts' products: self time
of the operations under the scope `moe_experts` (a fusion under the scope
of its root) inside executions of `generation_block_step`, over those
executions.  A Pallas kernel under the same scope would be read the same
way."""

from chipbench.scope_trace import scope_ms_per_execution


def read(obs):
    return scope_ms_per_execution(obs, "moe_experts",
                                  "generation_block_step")
