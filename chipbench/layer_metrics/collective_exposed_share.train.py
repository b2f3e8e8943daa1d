"""Share of the traced steps during which a collective was in flight and
no other operation of that chip ran, the chips' mean: the communication
the step does not hide behind its arithmetic.  Nothing on one chip."""

from chipbench.program_trace import session


def read(obs):
    red = session(obs)
    if not red or not red["collective_s"] or not red["window_s"]:
        return None
    return 100.0 * red["collective_s"]["exposed"] / red["window_s"]
