"""Share of the traced window in which no operation runs on the device:
1 minus the union of the device-op intervals over the window, %."""


def read(run):
    d = run.device
    if d is None or d.window_s <= 0:
        return None
    return 100.0 * (1.0 - d.busy_s / d.window_s)
