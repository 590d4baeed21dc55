"""pallas_roofline.<cell kind>: the point-op kernels' share of their
roofline, sum of least times over sum of kernel times, in percent.  Each
call's least time comes from its operations and bytes
(``bench/pointops.py``) and the chip's peaks."""
from bench import layers


def read(suffix, run):
    calls = layers.kernel_calls(run.reading["reduced"])
    if not calls:
        return None
    least, spent, bounds = layers.roofline(calls, run.chip.peaks)
    if spent <= 0:
        return None
    run.info(f"pallas_roofline.{suffix} least_s={least} kernel_s={spent} "
             f"bound_by={bounds}")
    return 100.0 * least / spent
