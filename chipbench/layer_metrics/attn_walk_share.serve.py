"""Mean share of the KV cache's slots x positions that a decode or verify
step's attention fetched (`generation_attn_walk_share`, one observation a
step, window only): the walk's granule is 4 slots x 128 positions, so it
follows how many slots are live and how long their requests have grown."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_attn_walk_share")
    return h and 100.0 * h["sum"] / h["count"]
