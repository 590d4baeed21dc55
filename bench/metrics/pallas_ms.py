"""pallas_ms.<cell kind>: device time of the point-op Pallas kernels per
unit of work (a serve microbatch or a training step)."""
from bench import layers


def read(suffix, run):
    calls = layers.kernel_calls(run.reading["reduced"])
    units = layers.units(run)
    if not calls or not units:
        return None
    return sum(c.dur_ns for c in calls) * 1e-6 / units
