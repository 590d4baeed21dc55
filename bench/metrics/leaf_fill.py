"""leaf_fill.<cell kind>: share of the stage-0 leaf slots of the served
clouds that hold a real leaf of the fractal partition, in percent: the sum
of ``leaves`` over the sum of ``leaf_slots``, both carried by each
``serve.execute`` span (absent where the engine runs no partition plan)."""
from bench import spans


def read(suffix, run):
    ex = [a for a in spans.args(run, spans.EXECUTE) if "leaf_slots" in a]
    slots = sum(a["leaf_slots"] for a in ex)
    if not slots:
        return None
    return 100.0 * sum(a["leaves"] for a in ex) / slots
