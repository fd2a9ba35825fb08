"""The metric arithmetic on synthetic scrapes and read logs."""

import arith

TEXT = """# TYPE stream_rows_total counter
stream_rows_total{job="q7"} %d
barrier_latency_seconds_count{job="q7"} %d
barrier_latency_seconds_sum{job="q7"} %f
barrier_phase_seconds_sum{job="q7",phase="seal"} %f
checkpoint_upload_seconds_total{job="q7"} 1.5
barrier_loop_errors_total 0
"""


def sample(t, rows, barriers, lat=0.0, seal=0.0):
    return {"t_req": t - 0.01, "t_resp": t,
            "m": arith.parse_scrape(TEXT % (rows, barriers, lat, seal))}


def test_parse_and_lookup():
    s = sample(1.0, 100, 2, 0.5, 0.25)
    assert arith.rows(s, "q7") == 100
    assert arith.barriers(s, "q7") == 2
    assert arith.metric(s["m"], "barrier_phase_seconds_sum", job="q7",
                        phase="seal") == 0.25
    assert arith.metric(s["m"], "barrier_loop_errors_total") == 0
    assert arith.metric(s["m"], "nothing", job="q7") is None
    assert len(arith.family(s["m"], "barrier_phase_seconds_sum",
                            job="q7")) == 1


def test_rate_is_between_first_and_last_barrier_seen():
    # barriers end at t=1, 2, 3.5 (a stall), 4; polls in between see
    # nothing new; the sample at t=4.4 shows no new barrier
    samples = [sample(1.0, 1000, 1), sample(1.5, 1000, 1),
               sample(2.0, 2000, 2), sample(2.5, 2000, 2),
               sample(3.5, 3000, 3), sample(4.0, 4000, 4),
               sample(4.4, 4000, 4)]
    edges = [samples[0]] + arith.barrier_edges(samples, "q7")
    assert [e["t_resp"] for e in edges] == [1.0, 2.0, 3.5, 4.0]
    # 3,000 rows between t=1 and t=4: the stall is in the denominator,
    # the idle tail after the last barrier is not
    assert arith.rate_between_barriers(edges, "q7") == 1000.0
    assert arith.rate_between_barriers(edges[:1], "q7") is None


def test_two_barriers_in_one_poll_count_once_as_an_edge():
    samples = [sample(1.0, 1000, 1), sample(2.0, 3000, 3)]
    edges = arith.barrier_edges(samples, "q7")
    assert len(edges) == 1
    assert arith.rows(edges[0], "q7") == 3000


def test_per_barrier_ms():
    a, b = sample(1.0, 0, 2, lat=1.0, seal=0.5), \
        sample(9.0, 0, 6, lat=3.0, seal=1.5)
    assert arith.per_barrier_ms(a, b, "q7",
                                "barrier_latency_seconds_sum") == 500.0
    assert arith.per_barrier_ms(a, b, "q7", "barrier_phase_seconds_sum",
                                phase="seal") == 250.0
    assert arith.per_barrier_ms(a, a, "q7",
                                "barrier_latency_seconds_sum") is None
    assert arith.per_barrier_ms(a, b, "q7", "no_such_counter") is None


def test_percentiles_nearest_rank():
    v = [float(i) for i in range(1, 101)]
    assert arith.percentile(v, 0.95) == 95.0
    assert arith.percentile(v, 0.5) == 50.0
    assert arith.percentile([7.0], 0.95) == 7.0
    assert arith.percentile([], 0.95) is None
    assert arith.median([1.0, 2.0, 10.0]) == 2.0


def test_failed_reads_have_no_latency_and_count_from_due_time():
    reads = [{"due": 1.0, "sent": 1.2, "done": 1.5, "ok": True},
             {"due": 2.0, "sent": 2.0, "done": 9.0, "ok": False},
             {"due": 3.0, "sent": 3.0, "done": 3.1, "ok": True}]
    lat = arith.read_latencies_ms(reads)
    assert [round(x) for x in lat] == [500, 100]


def test_every_seed_offers_the_same_arrivals_in_another_order():
    a = arith.schedule(10.0, 40.0, 4, 1.25, 0.5, seed=1)
    b = arith.schedule(10.0, 40.0, 4, 1.25, 0.5, seed=2)
    assert len(a) == 4 and all(len(s) == 50 for s in a)
    assert a != b
    for sa, sb in zip(a, b):
        # the same offsets from the even schedule, in another order
        off_a = sorted(round(t - i * 0.8, 6) for i, t in enumerate(sa))
        off_b = sorted(round(t - i * 0.8, 6) for i, t in enumerate(sb))
        assert off_a == off_b and len(set(off_a)) > 10
    # every read is due inside the window, in order
    for s in a:
        assert all(10.0 <= t <= 50.5 for t in s)
        assert s == sorted(s)
