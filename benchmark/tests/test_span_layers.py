"""The nine layer metrics that read the program's span counters
(``trace_span_seconds_total`` / ``trace_span_total``): the arithmetic on
a canned pair of scrapes, silence where the program has no such
counter (the parent commit), and the real server on the CPU."""

import pytest

import arith
import run

from test_rehearsal import rehearse

#: span -> (seconds, count) at the window's first and last barrier, for
#: job q7 (``JOB``) or for the process (``PROC``)
JOB = {
    "_maintain.device_wait": ((1.0, 10), (7.6, 20)),
    "drain_uploads": ((0.5, 10), (3.3, 20)),
    "ckpt_prepare": ((0.2, 10), (1.3, 20)),
    "ckpt_commit.encode": ((0.3, 10), (1.2, 20)),
    "ckpt_commit.put": ((0.1, 10), (0.6, 20)),
    "ckpt_commit.manifest": ((0.1, 10), (0.4, 20)),
}
PROC = {
    "tick": ((5.0, 10), (17.0, 20)),
    "tick.lock_wait": ((0.4, 10), (2.4, 20)),
    "read": ((9.0, 30), (50.0, 80)),
    "read.lock_wait": ((8.0, 30), (43.0, 80)),
    "read.execute": ((0.9, 30), (5.9, 80)),
}
WANT = {
    "tick_ms": 1200.0, "lock_gap_ms": 200.0, "device_wait_ms": 660.0,
    "upload_drain_ms": 280.0, "ckpt_fetch_ms": 110.0,
    "ckpt_encode_ms": 90.0, "ckpt_write_ms": 80.0,
    "read_lock_wait_ms": 700.0, "read_exec_ms": 100.0,
}


def scrape(which: int, spans: bool = True) -> dict:
    lines = ['barrier_latency_seconds_count{job="q7"} %d' % (10, 20)[which]]
    for series, label in ((JOB, 'job="q7",'), (PROC, "")):
        for span, ends in series.items() if spans else ():
            secs, n = ends[which]
            lines.append('trace_span_seconds_total{%sspan="%s"} %r'
                         % (label, span, secs))
            lines.append('trace_span_total{%sspan="%s"} %d.0'
                         % (label, span, n))
    return {"t_req": which, "t_resp": which + 0.5,
            "m": arith.parse_scrape("\n".join(lines) + "\n")}


def reader(name: str):
    return run.load_module(run.reader_path("per_layer", name)).read


@pytest.mark.parametrize("name", sorted(WANT))
def test_span_layer_on_a_canned_pair_of_scrapes(name):
    window = {"job": "q7", "scrape_start": scrape(0),
              "scrape_end": scrape(1)}
    assert reader(name)(window) == pytest.approx(WANT[name])
    # a program without the counter (the parent commit): nothing to
    # read, nothing raised, and the result line leaves the metric out
    bare = {"job": "q7", "scrape_start": scrape(0, spans=False),
            "scrape_end": scrape(1, spans=False)}
    assert reader(name)(bare) is None
    # no barrier, upload or read between the two scrapes: no mean
    still = {"job": "q7", "scrape_start": scrape(1), "scrape_end": scrape(1)}
    assert reader(name)(still) is None


def test_span_layers_read_the_real_server(tmp_path):
    """The served node on the CPU: every one of the nine finds its
    counter, and the tick closes over its parts."""
    _, window = rehearse(tmp_path, "tiny_q7_backlog", seed=7)
    got = {name: reader(name)(window) for name in WANT}
    assert all(v is not None and v >= 0 for v in got.values()), got
    barrier_ms = reader("barrier_ms")(window)
    assert got["tick_ms"] >= got["lock_gap_ms"] + barrier_ms \
        + got["upload_drain_ms"] - 1e-6
    assert got["device_wait_ms"] <= reader("seal_ms")(window)
    upload_ms = reader("upload_ms")(window)
    assert got["ckpt_fetch_ms"] + got["ckpt_encode_ms"] \
        + got["ckpt_write_ms"] <= upload_ms * 1.001
