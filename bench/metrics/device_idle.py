"""device_idle.<cell kind>: share of the traced window in which no
operation ran on the device, 1 - busy / window, in percent."""


def read(suffix, run):
    rd = run.reading
    red, window = rd["reduced"], rd["window"].seconds
    if not red.ops or window <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / window)
