"""Published peaks of the devices the benchmark may run on, keyed by
jax's ``device_kind``.  A device that is not listed is an error, never
a default.  (A copy of ``photon_ml_tpu/telemetry/device.py``'s table,
with the compute peak added from the same source.)"""

PEAKS = {
    "TPU v5 lite": {
        "hbm_gb_per_s": 819.0,
        "bf16_tflop_per_s": 197.0,
        "hbm_gb": 16.0,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB of HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to benchmark/harness/peaks.py with its source") from None
