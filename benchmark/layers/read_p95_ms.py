"""Front door, engine lock, read path: 95th percentile (nearest rank) of
the same reads as ``read_p50_ms``, from when each was due to its last
row.  The tail is the reads that waited out the window's one or two
longest ticks, so it swings too far from run to run to carry a bound;
it stands here, beside the median it should move with."""
import arith


def read(window):
    return arith.percentile(arith.read_latencies_ms(window["reads"]), 0.95)
