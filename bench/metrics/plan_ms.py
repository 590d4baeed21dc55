"""plan_ms.<cell kind>: device time of the jitted partition plan executable
per serve microbatch."""
from bench import layers


def read(suffix, run):
    red = run.reading["reduced"]
    plans = layers.plan_runs(red)
    if not plans:
        return None
    return sum(m.dur_ns for m in plans) * 1e-6 / len(plans)
