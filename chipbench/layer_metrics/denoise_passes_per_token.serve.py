"""Passes of the model over a slot's block (denoise and commit) for each
token streamed, in the window: `generation_block_passes_total` over
`generation_tokens_total`.  Under the `sequential` rule at
``block_length / denoising_steps`` = k positions a pass a request of
prompt p and output o costs, with r = p % B given in its first block,
ceil(g / k) denoise passes for each of its blocks (g the positions it
generates there: min(B - r, o) in the first, B in the whole ones, the rest
in the last) and one commit for every block but its last: 1.25 a token
for whole blocks of 4 at 4 steps, less the last block's commit
(`closed_form`; the tests hold the engine to it)."""

from chipbench.common import counter_delta


def closed_form(requests, block_length, steps):
    """Passes over tokens for ``[(prompt tokens, output tokens)]``."""
    per = block_length // steps
    passes = tokens = 0
    for p, o in requests:
        left, room = o, block_length - p % block_length
        while left > 0:
            g = min(room, left)
            left -= g
            passes += -(-g // per) + (1 if left > 0 else 0)
            room = block_length
        tokens += o
    return passes / tokens


def read(obs):
    tokens = counter_delta(obs, "generation_tokens_total")
    passes = counter_delta(obs, "generation_block_passes_total")
    return passes / tokens if passes and tokens else None
