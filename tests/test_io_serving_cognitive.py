"""HTTP / serving / cognitive tests against a local mock service.

Mirrors the reference test strategy (SURVEY.md §4.5): serving suites start
real local HTTP servers and POST to them.
"""
import json
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame, Transformer, Param


class MockService:
    """Echo-ish JSON server standing in for Azure endpoints (zero egress)."""

    def __init__(self):
        handler_self = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                handler_self.requests.append(
                    {"path": self.path, "headers": dict(self.headers), "body": body})
                if self.path.endswith("/fail"):
                    self.send_response(500)
                    self.end_headers()
                    return
                try:
                    payload = json.loads(body or b"null")
                except ValueError:
                    payload = {"raw_len": len(body)}
                resp = json.dumps({"echo": payload, "path": self.path}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(resp)))
                self.end_headers()
                self.wfile.write(resp)

            do_GET = do_POST

        self.requests = []
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    @property
    def url(self):
        return f"http://127.0.0.1:{self.httpd.server_port}"

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture()
def mock_service():
    s = MockService()
    yield s
    s.close()


def test_http_transformer(mock_service):
    from mmlspark_tpu.io import HTTPTransformer, HTTPRequestData
    col = np.empty(3, dtype=object)
    for i in range(3):
        col[i] = HTTPRequestData.post_json(mock_service.url + "/t", {"i": i})
    df = DataFrame.from_dict({"req": col})
    out = HTTPTransformer(input_col="req", output_col="resp").transform(df).collect()
    resp = out["resp"][1]
    assert resp["status_code"] == 200
    assert json.loads(resp["entity"].decode())["echo"] == {"i": 1}


def test_simple_http_transformer_and_errors(mock_service):
    from mmlspark_tpu.io import SimpleHTTPTransformer
    df = DataFrame.from_dict({"data": np.array([{"x": 1}, {"x": 2}], dtype=object)})
    t = SimpleHTTPTransformer(input_col="data", output_col="parsed",
                              url=mock_service.url + "/svc")
    out = t.transform(df).collect()
    assert out["parsed"][0]["echo"] == {"x": 1}
    assert out["errors"][0] is None
    # error path
    t2 = SimpleHTTPTransformer(input_col="data", output_col="parsed",
                               url=mock_service.url + "/fail")
    out2 = t2.transform(df).collect()
    assert out2["parsed"][0] is None
    assert out2["errors"][0]["status_code"] == 500


class AddReply(Transformer):
    def _transform(self, df):
        def per_part(p):
            out = np.empty(len(p["request"]), dtype=object)
            for i, r in enumerate(p["request"]):
                out[i] = {"double": 2 * r["value"]}
            return {**p, "reply": out}
        return df.map_partitions(per_part)


def _post(url, obj, timeout=10):
    req = urllib.request.Request(url, data=json.dumps(obj).encode(),
                                 headers={"Content-Type": "application/json"},
                                 method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def test_pipeline_server_continuous():
    from mmlspark_tpu.serving import PipelineServer
    server = PipelineServer(AddReply(), port=0, mode="continuous").start()
    try:
        for i in range(5):
            resp = _post(server.address, {"value": i})
            assert resp == {"double": 2 * i}
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/stats").read())
        assert stats["replied"] == 5
    finally:
        server.stop()


def test_pipeline_server_micro_batch_parallel():
    from mmlspark_tpu.serving import PipelineServer
    server = PipelineServer(AddReply(), port=0, mode="micro_batch",
                            micro_batch_interval_ms=30).start()
    results = {}

    def call(i):
        results[i] = _post(server.address, {"value": i})

    try:
        threads = [threading.Thread(target=call, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results[i] == {"double": 2 * i} for i in range(8))
    finally:
        server.stop()


def test_pipeline_server_micro_batch_deadline_flush():
    """Deadline-aware micro-batch trigger (ROADMAP PR 1 follow-up): an
    entry whose budget would expire before the trigger interval elapses
    flushes the batch early and gets scored, instead of aging into a
    certain 504 while the server idles out its interval."""
    import time as _time
    from mmlspark_tpu.serving import PipelineServer
    # the interval alone would sit on the request for 10 s — far past the
    # 2 s budget; the margin makes the flush land at ~1 s, budget intact
    server = PipelineServer(AddReply(), port=0, mode="micro_batch",
                            micro_batch_interval_ms=10_000,
                            micro_batch_deadline_margin_s=1.0).start()
    try:
        t0 = _time.monotonic()
        req = urllib.request.Request(
            server.address, data=json.dumps({"value": 21}).encode(),
            headers={"Content-Type": "application/json",
                     "X-MMLSpark-Deadline-Ms": "2000"}, method="POST")
        with urllib.request.urlopen(req, timeout=8) as r:
            assert json.loads(r.read().decode()) == {"double": 42}
        elapsed = _time.monotonic() - t0
        assert elapsed < 5.0, \
            f"flush waited {elapsed:.1f}s — deadline trigger did not fire"
    finally:
        server.stop()


def test_text_sentiment_against_mock(mock_service):
    from mmlspark_tpu.cognitive import TextSentiment
    df = DataFrame.from_dict({"text": np.array(["great product", "terrible"], dtype=object)})
    svc = TextSentiment(output_col="sentiment")
    svc.set("url", mock_service.url + "/text/analytics/v3.0/sentiment")
    svc.set("subscription_key", "fake-key")
    svc.set_col("text", "text")
    out = svc.transform(df).collect()
    body = out["sentiment"][0]["echo"]
    assert body["documents"][0]["text"] == "great product"
    # key header was sent
    assert mock_service.requests[0]["headers"]["Ocp-Apim-Subscription-Key"] == "fake-key"


def test_cognitive_error_column(mock_service):
    from mmlspark_tpu.cognitive import TextSentiment
    df = DataFrame.from_dict({"text": np.array(["x"], dtype=object)})
    svc = TextSentiment(output_col="s")
    svc.set("url", mock_service.url + "/fail")
    svc.set("subscription_key", "k")
    svc.set_col("text", "text")
    out = svc.transform(df).collect()
    assert out["s"][0] is None
    assert out["error"][0]["status_code"] == 500


def test_anomaly_translate_bing_request_shapes(mock_service):
    from mmlspark_tpu.cognitive import DetectLastAnomaly, Translate, BingImageSearch
    series = [{"timestamp": f"2024-01-0{i+1}T00:00:00Z", "value": float(i)} for i in range(5)]
    ser_col = np.empty(1, dtype=object)
    ser_col[0] = series
    df = DataFrame.from_dict({"series": ser_col,
                              "q": np.array(["cats"], dtype=object),
                              "txt": np.array(["hola"], dtype=object)})
    an = DetectLastAnomaly(output_col="anomaly")
    an.set("url", mock_service.url + "/anomaly")
    an.set("subscription_key", "k")
    an.set_col("series", "series")
    assert an.transform(df).collect()["anomaly"][0]["echo"]["granularity"] == "daily"

    tr = Translate(output_col="translated")
    tr.set("url", mock_service.url + "/translate?api-version=3.0")
    tr.set("subscription_key", "k")
    tr.set_col("text", "txt")
    tr.set("to_language", ["fr", "de"])
    out = tr.transform(df).collect()["translated"][0]
    assert out["echo"] == [{"Text": "hola"}]
    assert "to=fr&to=de" in out["path"]

    bi = BingImageSearch(output_col="images")
    bi.set("url", mock_service.url + "/bing")
    bi.set("subscription_key", "k")
    bi.set_col("query", "q")
    assert "q=cats" in bi.transform(df).collect()["images"][0]["path"]


def test_azure_search_and_powerbi(mock_service):
    from mmlspark_tpu.cognitive import AzureSearchWriter
    from mmlspark_tpu.io import powerbi
    df = DataFrame.from_dict({"id": np.array(["1", "2"], dtype=object),
                              "score": np.array([0.5, 0.9])})
    codes = AzureSearchWriter.write(df, "svc", "idx", "key",
                                    url_override=mock_service.url + "/search")
    assert codes == [200]
    sent = json.loads(mock_service.requests[-1]["body"])
    assert sent["value"][0]["@search.action"] == "mergeOrUpload"
    codes = powerbi.write(df, mock_service.url + "/powerbi")
    assert codes == [200]


def test_binary_and_image_io(tmp_path):
    from mmlspark_tpu.io import read_binary_files, read_images
    from PIL import Image
    import numpy as np
    (tmp_path / "sub").mkdir()
    (tmp_path / "a.bin").write_bytes(b"hello")
    (tmp_path / "sub" / "b.bin").write_bytes(b"world!")
    img = Image.fromarray(np.zeros((4, 6, 3), np.uint8))
    img.save(tmp_path / "img.png")
    df = read_binary_files(str(tmp_path), pattern="*.bin")
    got = df.collect()
    assert got["bytes"][0] == b"hello" and got["bytes"][1] == b"world!"
    imgs = read_images(str(tmp_path), pattern="*.png")
    arr = imgs.collect()["image"][0]
    assert arr.shape == (4, 6, 3)


def test_binary_file_stream(tmp_path):
    """New files under a directory become micro-batch frames exactly once
    (reference BinaryFileFormat streaming)."""
    import time
    from mmlspark_tpu.io.binary import BinaryFileStream

    watched = tmp_path / "watched"
    watched.mkdir()

    def produce(name, data):
        # the producers' convention the stream documents: write under a
        # temporary name outside the directory, then rename into it.  A
        # file written in place can be polled between its creation and
        # its write, and is then delivered empty, once.
        (tmp_path / (name + ".tmp")).write_bytes(data)
        (tmp_path / (name + ".tmp")).rename(watched / name)

    produce("a.bin", b"alpha")
    stream = BinaryFileStream(str(watched), poll_interval_s=0.05)
    b1 = stream.get_batch()
    assert sorted(p.split("/")[-1] for p in b1.collect()["path"]) == ["a.bin"]
    assert stream.get_batch() is None  # no new files -> no batch

    got = []
    handle = stream.for_each_batch(
        lambda df: got.extend(bytes(b) for b in df.collect()["bytes"]))
    produce("b.bin", b"beta")
    produce("c.bin", b"gamma")
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and len(got) < 2:
        time.sleep(0.05)
    handle.stop()
    assert sorted(got) == [b"beta", b"gamma"]  # a.bin already delivered
    assert handle.last_error is None
