"""Device layer: peak bytes in use on the fullest chip after the measured
window (``device.memory_stats()``), in GB. Recorded; memory headroom is what
caps the batch."""


def reduce(run: dict):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
