"""The chip's published peaks, keyed by JAX's `device_kind`. The
benchmark's own copy (the program's is obs/perf.PEAK_BF16_FLOPS), so no
later PR can move the yardstick. A kind that is not here is an error,
not a default."""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s per chip.
PEAKS = {
    'TPU v5 lite': {'bf16_flops': 197e12, 'hbm_bytes_s': 819e9,
                    'source': 'cloud.google.com/tpu/docs/v5e'},
}


def peaks_of(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError('no published peaks for device kind %r: add a row '
                       'with its source to benchmarks/harness/peaks.py'
                       % (device_kind,))
