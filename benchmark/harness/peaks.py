"""The one table of device peaks, keyed by ``device_kind``.

A device that is not in the table is an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s per chip. jax reports the chip as "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no peaks recorded for device kind %r (known: %s): add a row "
            "with its source to benchmark/harness/peaks.py"
            % (device_kind, sorted(PEAKS))) from None


def roofline_pct(flops, bytes_moved, seconds, peaks):
    """Share (%) of the roofline a kernel reached: the least time the chip
    could take for the operations and bytes the algorithm needs, over the
    time it took. None where there is no time to divide by."""
    if not seconds or seconds <= 0:
        return None
    least = max(flops / peaks["bf16_flops"],
                bytes_moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
