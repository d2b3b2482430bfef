"""Open-loop HTTP traffic against ``PipelineServer``: requests are due at the
times of a seeded Poisson process of a fixed rate, whatever the server does,
and each is timed from the instant it was DUE, so that a stall shows in the
latency of every request it delayed.

The load comes from a child process that never touches JAX (this file, run as
a program): the server's handler threads then share their interpreter with no
client.  ``connections`` persistent connections take the requests in the order
they fall due; when all are busy a request waits and its wait counts.  The
child reports how late each send ran, so a starved generator is not read as a
fast server.

The traffic file gives ``rate_per_s``, ``connections``, ``timeout_s``,
``pool_size``, ``sample_requests``, ``warm_batches``, the ``server``
options, ``slice_seconds`` and ``trace_seconds``.  ``p50_ms`` and ``p95_ms``
are medians over the window's slices of ``slice_seconds`` (by due time) of
each slice's percentile (``measure.sliced_percentile``).
"""
from __future__ import annotations

import http.client
import json
import math
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ------------------------------------------------------------ the generator

def drive(host: str, port: int, path: str, bodies: Sequence[bytes],
          picks: Sequence[int], due_s: Sequence[float], connections: int,
          timeout_s: float, keep_replies: Sequence[int] = (),
          warm_requests: int = 0, wait_for_start=None) -> Dict[str, Any]:
    """Send request ``i`` (body ``bodies[picks[i]]``) ``due_s[i]`` seconds
    after the start, over ``connections`` persistent connections.  The start
    is when ``wait_for_start`` returns; it is called once every connection
    is open and has sent its ``warm_requests``.  Returns per request
    ``sent_s`` and ``done_s`` (seconds after the start; NaN when it never got
    that far) and ``status`` (the HTTP status, -1 for a failed or timed-out
    exchange), and the reply bodies asked for."""
    n = len(due_s)
    sent = [math.nan] * n
    done = [math.nan] * n
    status = [0] * n
    replies: Dict[int, str] = {}
    keep = set(int(i) for i in keep_replies)
    lock = threading.Lock()
    cursor = [0]
    t0 = [0.0]
    ready_count = threading.Barrier(connections + 1, timeout=300)
    go = threading.Event()
    headers = {"Content-Type": "application/octet-stream"}

    def exchange(conn, body):
        conn.request("POST", path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()

    def worker(w: int) -> None:
        conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
        for k in range(warm_requests):
            exchange(conn, bodies[(w + k) % len(bodies)])
        ready_count.wait()
        go.wait()
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= n:
                break
            wait = t0[0] + due_s[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter() - t0[0]
            try:
                status[i], data = exchange(conn, bodies[picks[i]])
                if i in keep:
                    replies[i] = data.decode()
            except (OSError, http.client.HTTPException):
                status[i] = -1
                conn.close()
                conn = http.client.HTTPConnection(host, port,
                                                  timeout=timeout_s)
            done[i] = time.perf_counter() - t0[0]
        conn.close()

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(connections)]
    for t in threads:
        t.start()
    ready_count.wait()
    if wait_for_start is not None:
        wait_for_start()
    t0[0] = time.perf_counter()
    go.set()
    for t in threads:
        t.join()
    return {"sent_s": sent, "done_s": done, "status": status,
            "replies": {str(k): v for k, v in replies.items()}}


def plan(mix: Dict[str, Any], seed: int, rate_per_s: float, seconds: float
         ) -> Dict[str, Any]:
    """Due times, the image each request carries and the requests whose
    replies are checked: all from the seed."""
    from benchmark import datagen
    due = datagen.poisson_arrivals(seed, rate_per_s, seconds)
    picks = datagen.picks(seed, len(due), int(mix["pool_size"]))
    sample = datagen.stream(seed, datagen.STREAM_SAMPLE).choice(
        len(due), size=min(int(mix["sample_requests"]), len(due)),
        replace=False)
    return {"due_s": due.tolist(), "picks": picks.tolist(),
            "sample": sorted(int(i) for i in sample)}


def _child_main() -> None:
    """The load generator as a process: reads one JSON line of arguments,
    prints READY when its connections are open and warm, starts on GO, and
    prints the outcome as one JSON line."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.manifest import Manifest
    args = json.loads(sys.stdin.readline())
    manifest = Manifest(args["root"])
    family = manifest.module("families", args["config"]["family"])
    bodies = family.request_bodies(args["config"], args["mix"], args["seed"])
    p = args["plan"]

    def wait_for_start():
        print("READY", flush=True)
        if sys.stdin.readline().strip() != "GO":
            os._exit(3)

    out = drive(args["host"], args["port"], args["path"], bodies, p["picks"],
                p["due_s"], int(args["mix"]["connections"]),
                float(args["mix"]["timeout_s"]), keep_replies=p["sample"],
                warm_requests=int(args["mix"]["warm_requests"]),
                wait_for_start=wait_for_start)
    print(json.dumps(out), flush=True)


class Client:
    """The parent's handle on the generator process."""

    def __init__(self, run, server, seed: int, plan: Dict[str, Any]):
        self.args = {"root": run.manifest.root, "config": run.config,
                     "mix": run.mix, "seed": seed, "host": server.host,
                     "port": server.port, "path": server.api_path,
                     "plan": plan}
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("MMLSPARK_TPU_")}
        env["JAX_PLATFORMS"] = "cpu"        # the child must never take a chip
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, env=env)
        self.proc.stdin.write(json.dumps(self.args) + "\n")
        self.proc.stdin.flush()

    def wait_ready(self) -> None:
        line = self.proc.stdout.readline().strip()
        if line != "READY":
            raise RuntimeError(f"load generator said {line!r}, exit code "
                               f"{self.proc.poll()}")

    def go(self) -> None:
        self.proc.stdin.write("GO\n")
        self.proc.stdin.flush()

    def result(self) -> Dict[str, Any]:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"load generator ended without a result, "
                               f"exit code {self.proc.wait()}")
        return json.loads(line)

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            if pipe:
                pipe.close()


# ---------------------------------------------------------- the served side

def start_server(run, system):
    """``PipelineServer`` over the model the family serves, warmed by the
    family for every batch shape the traffic uses."""
    from mmlspark_tpu.serving import PipelineServer
    model = system.warm_up_serving()
    opts = run.mix["server"]
    return PipelineServer(
        model, input_col="request", reply_col="reply", port=0,
        mode=opts["mode"], max_batch=int(opts["max_batch"]),
        input_parser=system.request_parser(),
        request_timeout_s=float(run.mix["timeout_s"])).start()


def measure_window(run, server, rate_per_s: float, seconds: float,
                   seed: int, on_ready=None, window=None) -> Dict[str, Any]:
    """One window of traffic at one rate against a running server."""
    import contextlib
    planned = plan(run.mix, seed, rate_per_s, seconds)
    client = Client(run, server, seed, planned)
    try:
        client.wait_ready()
        if on_ready:
            on_ready()
        with (window() if window else contextlib.nullcontext()):
            client.go()
            out = client.result()
    finally:
        client.close()
    out.update(planned)
    return out


def summarize(out: Dict[str, Any], timeout_s: float, seconds: float,
              slice_s: float = 0.0) -> Dict[str, Any]:
    """Latencies from due time, misses, lateness of the generator.  A request
    without a 200 reply counts at the client's time limit everywhere."""
    from benchmark import measure
    miss_ms = timeout_s * 1e3
    lat_ms = [(d - due) * 1e3 if s == 200 else miss_ms for s, d, due
              in zip(out["status"], out["done_s"], out["due_s"])]
    late_ms = [(out["sent_s"][i] - out["due_s"][i]) * 1e3
               for i in range(len(out["due_s"]))
               if not math.isnan(out["sent_s"][i])]
    ok = sum(s == 200 for s in out["status"])
    last_done = max((d for d in out["done_s"] if not math.isnan(d)),
                    default=math.nan)
    p50, p50_slices = measure.sliced_percentile(out["due_s"], lat_ms, 50,
                                                slice_s, seconds)
    p95, p95_slices = measure.sliced_percentile(out["due_s"], lat_ms, 95,
                                                slice_s, seconds)
    return {"attempted": len(lat_ms), "misses": len(lat_ms) - ok,
            "p50_ms": p50, "p95_ms": p95,
            "p50_ms_slices": p50_slices, "p95_ms_slices": p95_slices,
            "window_p50_ms": measure.percentile(lat_ms, 50),
            "window_p95_ms": measure.percentile(lat_ms, 95),
            "p99_ms": measure.percentile(lat_ms, 99),
            "late_ms_p99": measure.percentile(late_ms, 99) if late_ms
            else math.nan,
            "completed_per_s": ok / last_done if last_done > 0
            else math.nan}


def run(run, family) -> Dict[str, Any]:
    mix = run.mix
    system = family.build(run)
    server = start_server(run, system)
    try:
        out = measure_window(run, server, float(mix["rate_per_s"]),
                             run.seconds, run.seed, on_ready=run.setup_done,
                             window=run.window)
    finally:
        server.stop()
    summary = summarize(out, float(mix["timeout_s"]), run.seconds,
                        float(mix["slice_seconds"]))
    run.note(f"{summary['attempted']} requests at {mix['rate_per_s']}/s over "
             f"{run.seconds} s, {summary['misses']} without a reply; over "
             f"the whole window p50 {summary['window_p50_ms']:.3f} ms p95 "
             f"{summary['window_p95_ms']:.3f} ms p99 {summary['p99_ms']:.3f} "
             f"ms; generator late p99 {summary['late_ms_p99']:.3f} ms")
    for q in ("p50_ms", "p95_ms"):
        run.note(f"{q} {summary[q]:.3f} = median of the slices of "
                 f"{mix['slice_seconds']} s: "
                 + " ".join(f"{v:.2f}" for v in summary[q + "_slices"]))
    # the sampled replies against the family's reference for the same body
    wrong = system.wrong_replies(
        [(out["picks"][i], out["replies"][str(i)].encode())
         for i in out["sample"] if str(i) in out["replies"]])
    if summary["misses"]:
        run.fail(f"{summary['misses']} requests got no 200 reply")
    run.facts.update(summary, requests=summary["attempted"])
    return {"attempted": summary["attempted"],
            "failed": summary["misses"] + wrong,
            "end_to_end": {k: summary[k] for k in ("p50_ms", "p95_ms")}}


if __name__ == "__main__":
    _child_main()
