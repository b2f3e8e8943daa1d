"""Share of the decode-attention dispatch decisions (one a trace of a step
function) that took a Pallas kernel rather than the jnp reference."""


def read(obs):
    fam = obs["counters_after"].get("kernel_dispatch_total")
    if not fam:
        return None
    took = total = 0
    for s in fam["series"]:
        if "decode" in s["labels"].get("op", ""):
            total += s["value"]
            if s["labels"].get("impl") == "pallas":
                took += s["value"]
    return 100.0 * took / total if total else None
