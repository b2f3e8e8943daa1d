"""A per-layer metric a later PR would add: one file, found by its name."""


def read(obs):
    return obs.get("steps")
