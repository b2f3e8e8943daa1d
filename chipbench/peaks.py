"""Published peaks of the chips the benchmark knows, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error, never
a default: a utilization against a guessed peak is not a measurement."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s per chip.  (The program's own table,
    # observability.xla_cost.CHIP_PEAKS, also carries an ici_bw of
    # 45 GB/s that no measurement has reconciled; it is not copied.)
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def chip_peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device_kind %r in chipbench/peaks.py; "
            "add a row with its source (known: %s)"
            % (device_kind, sorted(PEAKS))) from None
