"""host_ms.<cell kind>: the serving engine's own host time per microbatch,
from its spans: every ``serve.admit``, plus every ``serve.execute`` less
the ``serve.sync`` inside it (the wait for the device), over the number of
``serve.execute`` spans, in ms."""
from bench import spans


def read(suffix, run):
    red = run.reading["reduced"]
    execs = spans.executions(red)
    if not execs:
        return None
    admit = sum(e - s for n, s, e in red.spans if n == spans.ADMIT)
    own = sum((ex[2] - ex[1]) - (sy[2] - sy[1]) for ex, sy in execs)
    return (admit + own) * 1e-6 / len(execs)
