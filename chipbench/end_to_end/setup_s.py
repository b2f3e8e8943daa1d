"""Process start to the first measured step or request: imports, weights,
the reference check, compilation or cache load, warm-up, and the ramp."""


def read(obs):
    return obs["setup_s"]
