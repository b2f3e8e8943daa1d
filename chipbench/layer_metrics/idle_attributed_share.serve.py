"""Share of the device's idle time in the traced stretch that lies under
one of the scheduler's own spans other than `generation.idle_wait`.  It
checks that the tracing covers the loop thread, and is near 100 by
construction wherever it does: `generation.step` is open whenever the loop
is not waiting for work, so idle in that span's own time, outside its
children, counts as named.  The split by span, which is what bounds a
gain, is the line `[program-spans]`.  Nothing for a program that opens no
scheduler span."""

from chipbench.program_trace import attributed_share, session


def read(obs):
    red = session(obs)
    if not red or "generation.step" not in red["span_seconds"]:
        return None
    return attributed_share(red["idle_s"])
