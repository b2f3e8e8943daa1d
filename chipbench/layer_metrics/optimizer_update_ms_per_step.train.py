"""Device time a step spends in operations under the scope
`optimizer_update`: their share of the traced steps times the median step
time.  A fusion counts under the scope of its root, so the update of a
matrix weight that XLA:TPU fuses into that weight's gradient matmul is
counted, and the matmul with it: the two are one operation on the device
(`program_trace.fusion_scope`; PERF.md, PR 26)."""

from chipbench.program_trace import ms_per_step


def read(obs):
    return ms_per_step(obs, "scope_s", "optimizer_update")
