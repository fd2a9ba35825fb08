"""Window program, device: device time of its XLA module, a run (one run
a barrier), from the trace."""


def read(window):
    tr = window["trace"]
    if not tr or not tr["window_program"]["runs"]:
        return None
    wp = tr["window_program"]
    return 1000.0 * wp["device_s"] / wp["runs"]
