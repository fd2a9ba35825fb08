"""Source rows the view took in, a second: ``stream_rows_total`` at the
last barrier seen in the window less at the first, over the time between
those two."""
import arith


def read(window):
    return arith.rate_between_barriers(window["edges"], window["job"])
