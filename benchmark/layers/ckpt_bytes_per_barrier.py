"""Checkpoint upload: bytes of the epoch objects (npz + meta) that the
disk showed committed between the window's first and last barrier, a
barrier.  Read from the data directory by the benchmark; nothing of the
program's counters."""


def read(window):
    epochs = window["epochs_on_disk"]
    if not epochs or window["barriers"] <= 0:
        return None
    return sum(epochs.values()) / window["barriers"]
