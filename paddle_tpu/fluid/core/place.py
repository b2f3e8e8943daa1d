"""Place: typed device identity.

Capability parity: reference `paddle/fluid/platform/place.h:26-98` defines
CPUPlace / CUDAPlace / CUDAPinnedPlace as a boost::variant and
`DeviceContextPool` (`device_context.h:513`) maps Place -> per-device context.

TPU-first design: a Place wraps a `jax.Device` (or is a symbolic request like
TPUPlace(0) resolved lazily).  There is no per-place stream/handle bundle —
XLA owns streams — so the "device context" collapses to the jax device plus
the executor's compiled-executable cache.
"""

import contextlib
import functools
import os


class Place:
    """Base class for device identities."""

    _kind = "undefined"
    _jax_platform = None

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    # -- resolution ---------------------------------------------------------
    def get_device(self):
        """Resolve to the concrete jax.Device this place names.  A place
        whose platform this process has no device of — or whose index is
        past the last one — is an error, never another device: a
        `TPUPlace` that ran on the CPU would report CPU results under
        the chip's name."""
        devs = _devices_by_platform(self._jax_platform)
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                "%r names no device: this process has %d %s device(s)"
                % (self, len(devs), self._jax_platform or "default"))
        return devs[self.device_id]

    # -- identity -----------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._kind == other._kind
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


@functools.lru_cache(maxsize=None)
def _devices_by_platform(platform):
    import jax

    if platform is None:
        return tuple(jax.devices())
    try:
        return tuple(jax.devices(platform))
    except RuntimeError:
        return ()


class CPUPlace(Place):
    _kind = "cpu"
    _jax_platform = "cpu"


class TPUPlace(Place):
    _kind = "tpu"
    _jax_platform = "tpu"


# Alias kept so code written against the reference API keeps working; on this
# framework "the accelerator place" is a TPU.
CUDAPlace = TPUPlace


# -- one process per chip ----------------------------------------------------
#
# An accelerator belongs to one process at a time: a parent that has
# touched JAX holds the chip, and a child that needs it then fails or
# hangs.  So a child that does NOT need the chip is pinned to the CPU
# before it imports jax, and a child that DOES is refused, with the
# reason, when its parent already holds one.


@contextlib.contextmanager
def cpu_only_children():
    """Processes started inside this block see ``JAX_PLATFORMS=cpu``
    (DataLoader workers and other helpers that never need the chip)."""
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if old is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = old


def check_children_can_take_chip(what, child_env=None):
    """Raise when this process already holds an accelerator and is about
    to start ``what``, children that would initialize a backend of their
    own (``child_env``: the environment they get; default ours).
    Children pinned to the CPU are always fine."""
    import jax
    from jax._src import xla_bridge

    env = os.environ if child_env is None else child_env
    if env.get("JAX_PLATFORMS") == "cpu":
        return
    if (xla_bridge.backends_are_initialized()
            and jax.default_backend() != "cpu"):
        raise RuntimeError(
            "cannot start %s: this process has already initialized the %s "
            "backend and holds the chip, which belongs to one process at "
            "a time.  Start them from a parent that has not touched jax, "
            "or pin them to the CPU with JAX_PLATFORMS=cpu in their "
            "environment" % (what, jax.default_backend()))


def default_place():
    """Accelerator if present, else CPU (cf. reference get_device logic)."""
    import jax

    d = jax.devices()[0]
    if d.platform == "cpu":
        return CPUPlace(0)
    return TPUPlace(0)


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


def tpu_device_count():
    import jax

    return len([d for d in jax.devices() if d.platform != "cpu"])
