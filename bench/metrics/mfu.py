"""mfu.<cell kind>: dense-layer FLOPs of the work done in the traced
window (real points only, x3 for a training step) over the device's busy
time there, as a share of the chip's bf16 peak, in percent."""


def read(suffix, run):
    rd = run.reading
    red = rd["reduced"]
    if not red.ops or red.busy_s <= 0 or not rd["work_flops"]:
        return None
    return 100.0 * rd["work_flops"] / red.busy_s / run.chip.peaks[
        "bf16_flops"]
