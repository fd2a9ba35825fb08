"""Device: 1 - union of the device's operation intervals over the traced
part of the window."""


def read(window):
    tr = window["trace"]
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
