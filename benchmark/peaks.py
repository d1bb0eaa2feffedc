"""Published peaks of the devices the benchmark may measure on, keyed by
``jax.devices()[0].device_kind``.  A device that is not here is an error,
never a default.  Source: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect per chip."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            "benchmark: device_kind %r is not in benchmark/peaks.py (%s); "
            "add its published peaks before measuring on it"
            % (device_kind, sorted(PEAKS)))
