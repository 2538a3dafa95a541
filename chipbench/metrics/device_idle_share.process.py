"""Share of the traced window in which no operation ran on the device.

Source: the profiler trace: one minus the union of the device's
operation intervals over the window from the first pass's start to the
last pass's end.
"""


def read(run):
    w = run.trace["window_s"]
    if w <= 0.0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / w)
