"""XLA cost attribution: measured FLOPs/bytes per executable -> MFU.

Every perf number in this repo so far derived FLOPs by hand (bench.py's
matmul-parameter model).  XLA already knows: a compiled executable's
`cost_analysis()` reports the flops and bytes the HLO actually contains
— after fusion, after the AMP casts, after whatever a pass pipeline did
to the program.  This module samples that into the shared registry and
into span metadata, so bench.py and the serving tier report MEASURED
utilization per executable:

* `cost_of_jitted(fn, *args)` — lower+compile a jitted callable for one
  argument signature (hits jax's compilation caches when the signature
  was already built, e.g. after warmup) and normalize
  `cost_analysis()`'s keys;
* `record_executable_cost(name, cost)` — gauges
  `xla_executable_flops{executable=}` /
  `xla_executable_bytes_accessed{executable=}`;
* `record_mfu(name, flops, seconds)` — the headline `mfu{executable=}`
  gauge: flops / seconds / peak.  Peak FLOP/s comes from an explicit
  argument, `$PADDLE_TPU_PEAK_FLOPS`, or `CHIP_PEAKS` — the one table
  of per-chip rates, keyed by `device_kind`, each figure with its
  source; a device it does not list is an error.

Sampling is warmup/once-per-signature work — nothing here runs on the
step path.
"""

from __future__ import annotations

import os

from .metrics import default_registry

__all__ = [
    "CHIP_PEAKS",
    "UnknownDeviceError",
    "chip_peaks",
    "cost_analysis_of",
    "cost_of_jitted",
    "feed_signature",
    "hbm_bandwidth",
    "ici_bandwidth",
    "record_executable_cost",
    "record_mfu",
    "peak_flops",
]


def feed_signature(feed):
    """Canonical (name, shape, dtype) cache key for one feed/batch
    dict.  The executable-cache writer and the cost-attribution reader
    must agree on this key byte-for-byte or attribution silently
    returns None — so every site (Predictor, InferenceServer,
    ShardedTrainStep) shares this one builder."""
    return tuple(sorted(
        (k, tuple(v.shape), str(v.dtype)) for k, v in feed.items()))

PEAK_FLOPS_ENV = "PADDLE_TPU_PEAK_FLOPS"
HBM_BW_ENV = "PADDLE_TPU_HBM_BW"
ICI_BW_ENV = "PADDLE_TPU_ICI_BW"
HOST_BW_ENV = "PADDLE_TPU_HOST_BW"

# THE table of per-chip rates, keyed by jax's `device_kind` — every MFU
# denominator and roofline axis in the repo (`record_mfu`,
# `analysis.perf.ChipSpec`) reads it.  A device that has no row is an
# error where a rate is asked for, never another chip's figures: an
# unknown TPU generation priced as a v5e reports a wrong utilization
# under a right-looking name.
CHIP_PEAKS = {
    "TPU v5 lite": {                    # one v5e chip
        "name": "tpu-v5e",
        # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
        # 16 GB HBM2e at 819 GB/s
        "peak_flops": 197e12,
        "hbm_bw": 819e9,
        # one ICI link, one direction: the scaling-book figure.  The
        # same Google Cloud page gives 1,600 Gbit/s per chip in total;
        # the two are not yet reconciled against a measured all-reduce
        # (ROADMAP S5)
        "ici_bw": 4.5e10,
        # host<->device link: a PCIe-gen3-x16-class assumption, not a
        # published figure (prices `fluid.host_embedding` exchanges)
        "host_bw": 1.6e10,
    },
    # the host CPU is a known device with no peak: utilization is not
    # defined there, and callers get None, not an error
    "cpu": None,
}


class UnknownDeviceError(LookupError):
    """A chip rate was asked for a device `CHIP_PEAKS` has no row for."""


def chip_peaks(device_kind=None):
    """The `CHIP_PEAKS` row for ``device_kind`` (default: the live
    ``jax.devices()[0].device_kind``); None for the host CPU; raises
    `UnknownDeviceError` for anything else the table does not list."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            "no published peaks for device_kind %r: add a row with its "
            "source to observability.xla_cost.CHIP_PEAKS (known: %s)"
            % (device_kind, sorted(CHIP_PEAKS))) from None


def _resolve_rate(explicit, env_name, axis, device_kind):
    """The shared resolution ladder for every chip-rate axis: explicit
    arg > env var > the `CHIP_PEAKS` row of ``device_kind`` (default:
    the live device).  None only for the host CPU."""
    if explicit:
        return float(explicit)
    env = os.getenv(env_name)
    if env:
        return float(env)
    row = chip_peaks(device_kind)
    return None if row is None else row[axis]


def peak_flops(explicit=None, device_kind=None):
    """Resolve the MFU denominator: explicit arg > $PADDLE_TPU_PEAK_FLOPS
    > `CHIP_PEAKS`."""
    return _resolve_rate(explicit, PEAK_FLOPS_ENV, "peak_flops",
                         device_kind)


def hbm_bandwidth(explicit=None, device_kind=None):
    """Resolve HBM bytes/s: explicit arg > $PADDLE_TPU_HBM_BW >
    `CHIP_PEAKS`."""
    return _resolve_rate(explicit, HBM_BW_ENV, "hbm_bw", device_kind)


def ici_bandwidth(explicit=None, device_kind=None):
    """Resolve ICI bytes/s (one link, one direction): explicit arg >
    $PADDLE_TPU_ICI_BW > `CHIP_PEAKS`."""
    return _resolve_rate(explicit, ICI_BW_ENV, "ici_bw", device_kind)


def host_bandwidth(explicit=None, device_kind=None):
    """Resolve host-link bytes/s (host-embedding exchange pricing):
    explicit arg > $PADDLE_TPU_HOST_BW > `CHIP_PEAKS`."""
    return _resolve_rate(explicit, HOST_BW_ENV, "host_bw", device_kind)


def cost_analysis_of(compiled):
    """Normalize `Compiled.cost_analysis()` -> {"flops": float,
    "bytes_accessed": float, ...} (keys snake_cased); None when the
    backend reports nothing."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if not isinstance(ca, dict) or not ca:
        return None
    out = {}
    for k, v in ca.items():
        k = str(k)
        # skip per-operand detail rows ("bytes accessed0{}", ...): the
        # headline numbers are what gauges/spans/stats want
        if "{" in k or not isinstance(v, (int, float)):
            continue
        out[k.replace(" ", "_")] = float(v)
    return out or None


def cost_of_jitted(fn, *args, **kwargs):
    """Cost analysis of the executable a jitted callable would run for
    these arguments.  `fn.lower(...)` only traces (nothing executes, no
    buffer is donated); `.compile()` reuses jax's executable caches when
    this signature was already built.  Returns None instead of raising —
    attribution is telemetry, never a failure source."""
    try:
        return cost_analysis_of(fn.lower(*args, **kwargs).compile())
    except Exception:
        return None


def record_executable_cost(name, cost, registry=None):
    """Publish one executable's cost into the registry; returns `cost`
    for chaining into span args."""
    if not cost:
        return cost
    reg = registry or default_registry()
    lbl = ("executable",)
    if "flops" in cost:
        reg.gauge("xla_executable_flops",
                  "HLO cost_analysis flops per execution",
                  labelnames=lbl).labels(name).set(cost["flops"])
    if "bytes_accessed" in cost:
        reg.gauge("xla_executable_bytes_accessed",
                  "HLO cost_analysis bytes accessed per execution",
                  labelnames=lbl).labels(name).set(cost["bytes_accessed"])
    return cost


def record_mfu(name, flops, seconds, peak=None, registry=None,
               device_kind=None):
    """Set `mfu{executable=name}` = flops/seconds/peak; returns the MFU
    (None on the host CPU, which has no peak, or when inputs are
    degenerate)."""
    if not flops or not seconds or seconds <= 0:
        return None
    peak = peak_flops(explicit=peak, device_kind=device_kind)
    if not peak:
        return None
    mfu = float(flops) / float(seconds) / peak
    reg = registry or default_registry()
    reg.gauge(
        "mfu",
        "Measured model FLOP utilization: cost_analysis flops / "
        "step time / peak", labelnames=("executable",),
    ).labels(name).set(mfu)
    return mfu
