"""The open-loop generator against a stub server: it keeps its schedule,
times from the due instant, reports its lateness and counts failures."""
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from benchmark import datagen
from benchmark.traffic import open_loop_http as olh


class _Stub:
    """Replies 200 with the body's length after ``delay_s``; request number
    ``fail_at`` (0-based, by arrival) gets a 500, ``drop_at`` a closed
    socket."""

    def __init__(self, delay_s=0.0, fail_at=(), drop_at=()):
        stub = self
        self.seen = 0
        self.lock = threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                with stub.lock:
                    k = stub.seen
                    stub.seen += 1
                time.sleep(delay_s)
                if k in drop_at:
                    self.connection.close()
                    return
                out = str(len(body)).encode()
                self.send_response(500 if k in fail_at else 200)
                self.send_header("Content-Length", str(len(out)))
                self.end_headers()
                self.wfile.write(out)

            def log_message(self, *_):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True)

    def __enter__(self):
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.thread.join(timeout=5)
        assert not self.thread.is_alive()

    @property
    def port(self):
        return self.httpd.server_port


def _drive(stub, due, connections=4, timeout_s=2.0, **kw):
    bodies = [b"x" * 10, b"y" * 20]
    picks = [i % 2 for i in range(len(due))]
    return olh.drive("127.0.0.1", stub.port, "/score", bodies, picks, due,
                     connections, timeout_s, **kw), picks


def test_requests_go_out_on_schedule_and_are_timed_from_when_due():
    due = [0.05 * i for i in range(20)]
    with _Stub(delay_s=0.01) as stub:
        out, picks = _drive(stub, due, keep_replies=[0, 3], warm_requests=1)
    assert out["status"] == [200] * 20
    sent, done = np.array(out["sent_s"]), np.array(out["done_s"])
    assert (sent >= np.array(due) - 1e-4).all()          # never early
    # and not far behind (loose: the suite shares its cores)
    assert np.median(sent - np.array(due)) < 0.25
    assert ((done - sent) >= 0.01).all()
    # the reply bodies asked for came back, for the right request
    assert out["replies"] == {"0": "10", "3": "20"}
    assert stub.seen == 20 + 4                           # one warm-up each
    s = olh.summarize(dict(out, due_s=due), timeout_s=2.0, seconds=1.0)
    assert s["attempted"] == 20 and s["misses"] == 0
    assert s["p50_ms"] >= 10 and s["late_ms_p99"] >= 0
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    assert s["completed_per_s"] == pytest.approx(20 / done.max())
    # unsliced, the medians of the slices are the whole window's percentiles
    assert (s["p50_ms"], s["p95_ms"]) == (s["window_p50_ms"],
                                          s["window_p95_ms"])
    halves = olh.summarize(dict(out, due_s=due), timeout_s=2.0, seconds=1.0,
                           slice_s=0.5)
    assert len(halves["p95_ms_slices"]) == 2
    assert halves["p95_ms"] == pytest.approx(
        sum(halves["p95_ms_slices"]) / 2)
    assert halves["p99_ms"] == s["p99_ms"]


def test_a_slow_server_shows_as_waiting_from_the_due_instant():
    # 10 requests due at once over 2 connections of a server that takes
    # 50 ms: the last waits for four before it; timing from the send would
    # hide that, timing from the due instant cannot
    due = [0.0] * 10
    with _Stub(delay_s=0.05) as stub:
        out, _ = _drive(stub, due, connections=2)
    s = olh.summarize(dict(out, due_s=due), timeout_s=2.0, seconds=1.0)
    sent, done = np.array(out["sent_s"]), np.array(out["done_s"])
    # every exchange takes its 50 ms, yet the tail is four exchanges longer:
    # the queue is counted, and the generator's lateness reports it
    assert s["p99_ms"] > 220 and s["late_ms_p99"] > 150
    assert s["p99_ms"] > (done - sent).min() * 1e3 + 150


def test_failures_and_dropped_connections_count_as_misses():
    due = [0.02 * i for i in range(30)]
    with _Stub(fail_at={5}, drop_at={11}) as stub:
        out, _ = _drive(stub, due, connections=1)
    status = out["status"]
    assert status.count(500) == 1 and status.count(-1) == 1
    assert status.count(200) == 28                       # it went on after
    s = olh.summarize(dict(out, due_s=due), timeout_s=2.0, seconds=1.0)
    assert s["misses"] == 2 and s["attempted"] == 30
    # two misses of thirty sit above the 95th percentile, at the time limit
    # (the dropped exchange also stalled the requests queued behind it)
    assert s["p99_ms"] >= 1900.0


def test_a_server_that_never_replies_times_out_as_a_miss():
    with _Stub(delay_s=0.6) as stub:
        out, _ = _drive(stub, [0.0, 0.0], connections=2, timeout_s=0.2)
    assert out["status"] == [-1, -1]
    s = olh.summarize(dict(out, due_s=[0.0, 0.0]), timeout_s=0.2, seconds=1.0)
    assert s["misses"] == 2 and s["p50_ms"] == 200.0
    assert math.isnan(s["completed_per_s"]) or s["completed_per_s"] == 0


def test_the_plan_comes_from_the_seed_alone():
    mix = {"pool_size": 16, "sample_requests": 5}
    a, b = olh.plan(mix, 7, 50.0, 4.0), olh.plan(mix, 7, 50.0, 4.0)
    c = olh.plan(mix, 8, 50.0, 4.0)
    assert a == b and a != c
    assert len(a["due_s"]) == 200 == len(a["picks"])
    assert len(a["sample"]) == 5 and max(a["sample"]) < 200
    assert a["due_s"] == datagen.poisson_arrivals(7, 50.0, 4.0).tolist()
