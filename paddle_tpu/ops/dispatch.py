"""Which implementation a kernel dispatch chose, and the rule that chose.

Every dispatch between a Pallas kernel and its XLA/jnp composition calls
`record` at trace time, so a compiled step's choice is never silent:

* ``kernel_dispatch_total{op, impl, rule}`` in the default metrics
  registry counts traces (not executions: a jitted step dispatches once,
  when it is traced);
* on a TPU, a choice of anything but the kernel also warns, once per
  call site and message (Python's default warning filter), naming the
  shape rule that decided.

`chip_smoke.py` prints `choices()` after each phase.
"""

import warnings

import jax

from ..observability.metrics import default_registry

__all__ = ["choices", "record"]

_NAME = "kernel_dispatch_total"


def record(op, impl, rule):
    """Count one dispatch of ``op`` to ``impl`` ("pallas" or the name of
    the composition that ran instead), decided by ``rule``."""
    default_registry().counter(
        _NAME, "Kernel-vs-composition dispatch decisions (per trace)",
        labelnames=("op", "impl", "rule")).labels(op, impl, rule).inc()
    if impl != "pallas" and jax.default_backend() == "tpu":
        warnings.warn("%s runs as %s, not the Pallas kernel: %s"
                      % (op, impl, rule), stacklevel=3)


def choices():
    """{(op, impl, rule): traces} so far in this process."""
    fam = default_registry().get(_NAME)
    if fam is None:
        return {}
    return {labels: int(child.value) for labels, child in fam._series()}
