"""Median over every read of the window, from when it was due to its
last row."""
import arith


def read(window):
    return arith.median(arith.read_latencies_ms(window["reads"]))
