"""The flash-attention kernels against their roofline, which compute
bounds, by a hair: exact attention does S/2 = 256 FLOPs a byte of q, k, v, o
and their gradients in bf16, against the chip's ridge of 197e12 / 819e9 =
240.  Closed-form FLOPs of exact attention,
forward plus twice that for the backward and nothing for recomputation
(chipbench/flops.py), over the bf16 peak, over the time the kernels took."""

from chipbench import flops
from chipbench.common import kernel_ms_per_step


def read(obs):
    ms = kernel_ms_per_step(obs)
    if not ms or not obs.get("peaks"):
        return None
    config, job = obs["config"], obs["traffic"]
    heads = config["num_attention_heads"]
    need = config["num_hidden_layers"] * flops.attention_flops(
        batch=job["global_batch"] // obs["chips"], heads=heads,
        seq=job["seq_len"], head_dim=config["hidden_size"] // heads,
        causal=False, backward=True)
    return 100.0 * need / obs["peaks"]["bf16_flops_per_s"] / (ms / 1e3)
