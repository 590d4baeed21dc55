"""queue_wait_ms.<cell kind>: time a request waited in the serving
engine's queue before its microbatch was dispatched, in ms: the sum of the
``wait_ms`` of every ``serve.execute`` span over the sum of its
``requests``."""
from bench import spans


def read(suffix, run):
    ex = spans.args(run, spans.EXECUTE)
    requests = sum(a["requests"] for a in ex)
    if not requests:
        return None
    run.info(f"queue microbatches={len(ex)} requests={requests} "
             f"slots={sum(a['slots'] for a in ex)} "
             f"depth_last={ex[-1]['depth']} "
             f"depth_max={max(a['depth'] for a in ex)}")
    return sum(a["wait_ms"] for a in ex) / requests
