"""Mean share of the engine's slots that held a request, sampled by the
benchmark every `occupancy_every_s` through the window."""


def read(obs):
    occ = obs["samples"]["occupancy"]
    return 100.0 * sum(occ) / len(occ) if occ else None
