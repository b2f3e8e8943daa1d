"""Mean share of the KV pool's blocks that held a request's tokens, sampled
with the occupancy: memory in use against memory reserved.  The engine
reserves slots x max_len tokens whatever the traffic sends."""


def read(obs):
    live = obs["samples"]["kv_pool_live"]
    return 100.0 * sum(live) / len(live) if live else None
