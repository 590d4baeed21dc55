"""idle_explained.<cell kind>: share of the device's idle time that falls
inside one of the serving engine's own spans, in percent.  Idle time is
every gap between operations on device 0 (as ``device_idle``'s busy time
is their union), between the first and the last operation of the trace."""
from bench import spans


def read(suffix, run):
    red = run.reading["reduced"]
    prog = [(s, e) for _, s, e in spans.program(red)]
    gaps = spans.idle_intervals(red)
    idle = sum(b - a for a, b in gaps)
    if not prog or idle <= 0:
        return None
    return 100.0 * spans.covered_ns(gaps, prog) / idle
