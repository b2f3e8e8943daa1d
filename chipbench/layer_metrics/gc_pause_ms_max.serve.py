"""The longest collection of the interpreter in the window, whichever
thread ran it (`generation_gc_pause_ms`, span `generation.gc`, one
observation a collection, its largest).  It is the whole process's, not
the scheduler's own: a collection holds the GIL, and so the serving
loop, for as long, and is filed under the layer it stalls."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_gc_pause_ms")
    return h and h["max"]
