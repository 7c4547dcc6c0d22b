"""The HTTP front door (serve/frontend.py + serve/admission.py, ROADMAP
item 1): priority + deadline headers propagate end-to-end, every failure
mode maps to a typed HTTP status, /healthz reflects breaker + queue state,
and `cli/serve.py --listen` survives real traffic and drains on SIGTERM
within serve.drain_timeout_s.

Most tests drive the real HTTP server over loopback against a pure-host
engine double (fast); the one subprocess test exercises the full
train-less path — bundle -> engine -> batcher -> admission -> HTTP -> drain
— with a real compiled engine and a real SIGTERM.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from yet_another_mobilenet_series_tpu.obs.registry import get_registry
from yet_another_mobilenet_series_tpu.serve.admission import AdmissionController
from yet_another_mobilenet_series_tpu.serve.faults import FaultyEngine
from yet_another_mobilenet_series_tpu.serve.frontend import Frontend
from yet_another_mobilenet_series_tpu.serve.pipeline import PipelinedBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _row_id_predict(images):
    return images[:, 0, 0, :1]


class _EchoEngine:
    def __init__(self, block=None):
        self.block = block

    def predict_async(self, images):
        block = self.block

        class _Handle:
            def result(_self):
                if block is not None:
                    assert block.wait(10)
                return _row_id_predict(images)

        return _Handle()

    def predict(self, images):
        return self.predict_async(images).result()


def _stack(engine=None, *, max_retries=2, breaker_threshold=5, breaker_cooldown_s=0.2,
           weights=(8.0, 3.0, 1.0), queue_depth=64, max_batch=8, reject_unmeetable=True):
    b = PipelinedBatcher(
        engine or _EchoEngine(), max_batch=max_batch, max_wait_ms=1.0,
        queue_depth=queue_depth, drain_timeout_s=2.0,
    ).start()
    ac = AdmissionController(
        b, weights=weights, max_retries=max_retries, retry_backoff_ms=1.0,
        breaker_threshold=breaker_threshold, breaker_cooldown_s=breaker_cooldown_s,
        reject_unmeetable=reject_unmeetable,
    )
    fe = Frontend(ac, port=0).start()
    return b, ac, fe


def _request(url, *, data=None, headers=None, method=None):
    """(status, parsed json body, response headers) without raising on 4xx/5xx."""
    req = urllib.request.Request(url, data=data, headers=headers or {}, method=method)
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _post_image(base, val, *, priority=None, deadline_ms=None):
    headers = {"Content-Type": "application/json"}
    if priority:
        headers["X-Priority"] = priority
    if deadline_ms is not None:
        headers["X-Deadline-Ms"] = str(deadline_ms)
    img = np.full((4, 4, 3), float(val), np.float32).tolist()
    return _request(base + "/predict", data=json.dumps({"image": img}).encode(), headers=headers)


# ---------------------------------------------------------------------------
# request/response semantics
# ---------------------------------------------------------------------------


def test_retry_after_on_overload_verdicts_and_typed_on_client():
    """Every overload-shaped 429/503 carries Retry-After (quota 429s,
    brownout 503s with the shed's OWN bound), the shared client surfaces it
    typed (ClientHTTPError.retry_after — the router's backpressure
    discriminator), and non-overload errors carry no header."""
    from yet_another_mobilenet_series_tpu.serve.brownout import build_ladder
    from yet_another_mobilenet_series_tpu.serve.client import ClientHTTPError, ReplicaClient

    get_registry().reset()
    blocker = threading.Event()
    b, ac, fe = _stack(_EchoEngine(block=blocker), weights=(98.0, 1.0, 1.0), queue_depth=8)
    client = ReplicaClient("127.0.0.1", fe.port)
    try:
        base = fe.url
        # a quota 429: with the engine blocked, concurrent best_effort
        # submits pile onto a 1-slot quota — overload-shaped -> Retry-After
        results = []
        lock = threading.Lock()

        def push():
            st, body, hdrs = _post_image(base, 1.0, priority="best_effort")
            with lock:
                results.append((st, body.get("error"), hdrs.get("Retry-After")))

        threads = [threading.Thread(target=push, daemon=True) for _ in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # let the stragglers hit the saturated quota
        blocker.set()
        for t in threads:
            t.join(timeout=15)
        statuses = list(results)
        quota_hits = [s for s in statuses if s[0] == 429]
        assert quota_hits, statuses
        assert all(ra is not None and float(ra) >= 0 for _, _, ra in quota_hits)
        # brownout shed: 503 + the policy's own Retry-After, typed on the client
        ac.apply_brownout(build_ladder(retry_after_s=7.0)[3])
        st, body, hdrs = _post_image(base, 1.0, priority="best_effort")
        assert st == 503 and body["error"] == "brownout"
        assert float(hdrs["Retry-After"]) == 7.0
        with pytest.raises(ClientHTTPError) as ei:
            client.predict(np.zeros((4, 4, 3), np.float32), priority="best_effort")
        assert ei.value.status == 503 and ei.value.tag == "brownout"
        assert ei.value.retry_after == 7.0
        ac.apply_brownout(build_ladder()[0])
        # a 400 (non-overload) carries no Retry-After
        st, _, hdrs = _request(base + "/predict", data=b"{}",
                               headers={"Content-Type": "application/json"})
        assert st == 400 and "Retry-After" not in hdrs
    finally:
        client.close()
        fe.stop()
        b.stop()


def test_healthz_reports_brownout_level():
    from yet_another_mobilenet_series_tpu.serve.brownout import build_ladder

    get_registry().reset()
    b, ac, fe = _stack(_EchoEngine())
    try:
        st, body, _ = _request(fe.url + "/healthz")
        assert st == 200 and body["brownout_level"] == 0
        assert body["brownout"]["level"] == 0
        get_registry().gauge("serve.brownout_level").set(4)
        ac.apply_brownout(build_ladder()[4])
        st, body, _ = _request(fe.url + "/healthz")
        assert st == 200  # degraded, not down: the breaker still gates 503
        assert body["brownout_level"] == 4
        assert body["brownout"]["shed_classes"] == ["batch", "best_effort"]
        assert body["brownout"]["retries_enabled"] is True
    finally:
        fe.stop()
        b.stop()


def test_predict_json_round_trip_with_priority_and_deadline():
    b, ac, fe = _stack()
    try:
        status, doc, _ = _post_image(fe.url, 7, priority="batch", deadline_ms=5000)
        assert status == 200
        assert doc["priority"] == "batch"
        assert doc["logits"] == [7.0]
        snap = get_registry().snapshot()
        assert snap["serve.requests.batch"] >= 1  # the header reached admission
        assert snap["serve.latency_seconds.batch.count"] >= 1
    finally:
        fe.stop()
        b.stop()


def test_predict_raw_tensor_body():
    b, ac, fe = _stack()
    try:
        img = np.full((4, 4, 3), 5.0, np.float32)
        status, doc, _ = _request(
            fe.url + "/predict", data=img.tobytes(),
            headers={"Content-Type": "application/octet-stream", "X-Shape": "4,4,3"},
        )
        assert status == 200 and doc["logits"] == [5.0]
        # shape mismatch is a 400, not a crash
        status, doc, _ = _request(
            fe.url + "/predict", data=img.tobytes(),
            headers={"Content-Type": "application/octet-stream", "X-Shape": "8,8,3"},
        )
        assert status == 400 and doc["error"] == "bad_request"
    finally:
        fe.stop()
        b.stop()


def test_predict_u8_wire_via_x_dtype_header():
    """X-Dtype: u8 carries RAW uint8 pixels end-to-end — the quantized
    wire's 4x byte drop crossing the HTTP edge intact (a u8 body is a
    quarter the bytes of the same image as f4) — and the typed client
    sends it automatically for uint8 arrays. Unknown codes are a 400."""
    from yet_another_mobilenet_series_tpu.serve.client import ReplicaClient

    b, ac, fe = _stack()
    try:
        img_u8 = np.full((4, 4, 3), 200, np.uint8)
        body = img_u8.tobytes()
        assert len(body) == 4 * 4 * 3  # a quarter of the f4 wire's 192
        status, doc, _ = _request(
            fe.url + "/predict", data=body,
            headers={"Content-Type": "application/octet-stream",
                     "X-Shape": "4,4,3", "X-Dtype": "u8"},
        )
        assert status == 200 and doc["logits"] == [200.0]
        # the shared client picks the code from the array dtype
        client = ReplicaClient("127.0.0.1", fe.port, timeout_s=10.0)
        assert client.predict(img_u8).tolist() == [200.0]
        client.close()
        # absent header = the f4 contract (pre-header clients keep working)
        f4 = np.full((4, 4, 3), 7.0, np.float32)
        status, doc, _ = _request(
            fe.url + "/predict", data=f4.tobytes(),
            headers={"Content-Type": "application/octet-stream", "X-Shape": "4,4,3"},
        )
        assert status == 200 and doc["logits"] == [7.0]
        # unknown dtype codes and a u8-sized body declared f4 are 400s
        status, doc, _ = _request(
            fe.url + "/predict", data=body,
            headers={"Content-Type": "application/octet-stream",
                     "X-Shape": "4,4,3", "X-Dtype": "f2"},
        )
        assert status == 400 and "X-Dtype" in doc["message"]
        status, doc, _ = _request(
            fe.url + "/predict", data=body,
            headers={"Content-Type": "application/octet-stream", "X-Shape": "4,4,3"},
        )
        assert status == 400 and doc["error"] == "bad_request"
    finally:
        fe.stop()
        b.stop()


def test_membership_endpoints_register_deregister():
    """POST /register|/deregister serve the TTL-lease protocol when the
    admission object speaks it (the fleet Router); a plain replica answers
    404 so a misconfigured heartbeat is loud."""
    from yet_another_mobilenet_series_tpu.serve.client import ClientHTTPError, ReplicaClient

    # a plain replica: 404
    b, ac, fe = _stack()
    try:
        client = ReplicaClient("127.0.0.1", fe.port, timeout_s=10.0)
        with pytest.raises(ClientHTTPError) as ei:
            client.register("127.0.0.1", 9999, ttl_s=5.0)
        assert ei.value.status == 404
        client.close()
    finally:
        fe.stop()
        b.stop()

    # a router-shaped admission: the lease round-trips over the wire
    class _FakeRouterAdmission:
        def __init__(self):
            self.calls = []

        def submit(self, image, **kw):
            raise AssertionError("not exercised here")

        def state(self):
            return {"breaker_state": 0, "queued_total": 0}

        def register(self, host, port, *, ttl_s=None, replica_id=""):
            if ttl_s is not None and ttl_s <= 0:
                raise ValueError("lease ttl_s must be > 0")
            self.calls.append(("register", host, port, ttl_s, replica_id))
            return {"ok": True, "key": f"{host}:{port}", "ttl_s": ttl_s or 5.0,
                    "new": True, "source": "lease", "replica_id": replica_id}

        def deregister(self, host, port):
            self.calls.append(("deregister", host, port))
            return {"ok": True, "key": f"{host}:{port}"}

    fake = _FakeRouterAdmission()
    fe2 = Frontend(fake, port=0, replica_id="router").start()
    try:
        client = ReplicaClient("127.0.0.1", fe2.port, timeout_s=10.0)
        doc = client.register("127.0.0.1", 9001, ttl_s=2.5, replica_id="r-x")
        assert doc["ok"] and doc["ttl_s"] == 2.5
        doc = client.deregister("127.0.0.1", 9001)
        assert doc["ok"]
        assert fake.calls == [("register", "127.0.0.1", 9001, 2.5, "r-x"),
                              ("deregister", "127.0.0.1", 9001)]
        # malformed bodies and rejected leases map to 400
        status, doc, _ = _request(fe2.url + "/register", data=b"not json",
                                  headers={"Content-Type": "application/json"})
        assert status == 400 and doc["error"] == "bad_request"
        status, doc, _ = _request(
            fe2.url + "/register",
            data=json.dumps({"host": "127.0.0.1", "port": 9001, "ttl_s": -1}).encode(),
            headers={"Content-Type": "application/json"})
        assert status == 400 and "ttl_s" in doc["message"]
        client.close()
    finally:
        fe2.stop()


def test_malformed_requests_get_400_and_404():
    b, ac, fe = _stack()
    try:
        for payload in [b"not json", json.dumps({"not_image": 1}).encode(),
                        json.dumps({"image": [1.0, 2.0]}).encode()]:
            status, doc, _ = _request(fe.url + "/predict", data=payload,
                                      headers={"Content-Type": "application/json"})
            assert status == 400 and doc["error"] == "bad_request"
        status, doc, _ = _post_image(fe.url, 1, priority="platinum")
        assert status == 400 and "platinum" in doc["message"]
        assert _request(fe.url + "/nope", data=b"x")[0] == 404
        assert _request(fe.url + "/nope")[0] == 404
    finally:
        fe.stop()
        b.stop()


def test_deadline_shed_maps_to_504():
    gate = threading.Event()
    b = PipelinedBatcher(_EchoEngine(block=gate), max_batch=1, max_inflight=1,
                         max_wait_ms=0.0, queue_depth=64, drain_timeout_s=5.0).start()
    ac = AdmissionController(b, max_retries=2, retry_backoff_ms=1.0, reject_unmeetable=False)
    fe = Frontend(ac, port=0).start()
    try:
        # request 0 wedges the single in-flight slot; request 1's deadline
        # expires while it waits behind it -> shed -> 504
        responses = {}

        def post(i, deadline_ms):
            responses[i] = _post_image(fe.url, i, deadline_ms=deadline_ms)

        slow = threading.Thread(target=post, args=(0, 30000), daemon=True)
        doomed = threading.Thread(target=post, args=(1, 40.0), daemon=True)
        slow.start()
        time.sleep(0.1)
        doomed.start()
        time.sleep(0.2)  # deadline 1 expires while the window is wedged
        gate.set()
        slow.join(timeout=30)
        doomed.join(timeout=30)
        assert responses[0][0] == 200
        status, doc, _ = responses[1]
        assert status == 504 and doc["error"] == "deadline_exceeded"
    finally:
        gate.set()
        fe.stop()
        b.stop()


def test_breaker_drill_over_http_and_healthz():
    """Engine errors surface as 500s, the streak opens the breaker (503 +
    Retry-After, healthz flips to 503/open), the cooldown probe closes it
    (healthz back to 200/closed)."""
    eng = FaultyEngine(_EchoEngine(), fail_first_n=3)
    b, ac, fe = _stack(eng, max_retries=0, breaker_threshold=3, breaker_cooldown_s=0.3)
    try:
        status, doc, _ = _request(fe.url + "/healthz")
        assert status == 200 and doc["ok"] and doc["breaker"] == "closed"
        assert set(doc["classes"]) == {"interactive", "batch", "best_effort"}
        for _ in range(3):
            status, doc, _ = _post_image(fe.url, 1)
            assert status == 500 and doc["error"] == "engine_error"
        status, doc, headers = _post_image(fe.url, 1)
        assert status == 503 and doc["error"] == "breaker_open"
        assert float(headers["Retry-After"]) >= 0
        status, doc, _ = _request(fe.url + "/healthz")
        assert status == 503 and doc["breaker"] == "open" and not doc["ok"]
        time.sleep(0.35)  # cooldown -> the next predict is the half-open probe
        status, doc, _ = _post_image(fe.url, 6)
        assert status == 200 and doc["logits"] == [6.0]
        status, doc, _ = _request(fe.url + "/healthz")
        assert status == 200 and doc["breaker"] == "closed"
    finally:
        fe.stop()
        b.stop()


def test_class_quota_rejections_map_to_429():
    """best_effort floods 429 at their weighted share while interactive
    still admits — the QoS point of per-class admission."""
    gate = threading.Event()
    b, ac, fe = _stack(_EchoEngine(block=gate), weights=(8.0, 3.0, 1.0),
                       queue_depth=12, max_batch=1)
    try:
        results = {"ok_or_pending": 0, "rejected": 0}
        lock = threading.Lock()

        def flood(i):
            status, doc, _ = _post_image(fe.url, i, priority="best_effort", deadline_ms=30000)
            with lock:
                if status == 429:
                    assert doc["error"] == "queue_full"
                    results["rejected"] += 1
                else:
                    results["ok_or_pending"] += 1

        threads = [threading.Thread(target=flood, args=(i,), daemon=True) for i in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # floods are queued/rejected; engine still wedged
        # interactive has its own share: admitted despite the flood
        status_doc = {}

        def interactive():
            status_doc["r"] = _post_image(fe.url, 9, priority="interactive", deadline_ms=30000)

        it = threading.Thread(target=interactive, daemon=True)
        it.start()
        time.sleep(0.2)
        gate.set()
        it.join(timeout=30)
        for t in threads:
            t.join(timeout=30)
        status, doc, _ = status_doc["r"]
        assert status == 200 and doc["logits"] == [9.0]
        assert results["rejected"] >= 1  # the flood hit its quota
    finally:
        gate.set()
        fe.stop()
        b.stop()


def test_reject_unmeetable_deadline_at_arrival():
    """Once the latency EWMA knows the service is slow, a request whose
    deadline cannot be met is rejected at ARRIVAL (429 deadline_unmeetable),
    before burning a queue slot."""
    class _Slow(_EchoEngine):
        def predict_async(self, images):
            time.sleep(0.05)
            return super().predict_async(images)

    b, ac, fe = _stack(_Slow(), max_batch=1)
    try:
        assert _post_image(fe.url, 1)[0] == 200  # teaches the EWMA ~50ms
        assert ac.predicted_wait_s() > 0.01
        status, doc, _ = _post_image(fe.url, 2, deadline_ms=1.0)
        assert status == 429 and doc["error"] == "deadline_unmeetable"
        assert get_registry().snapshot()["serve.rejected_deadline"] >= 1
        # a meetable deadline still admits
        assert _post_image(fe.url, 3, deadline_ms=30000)[0] == 200
    finally:
        fe.stop()
        b.stop()


def test_concurrent_http_clients_route_rows():
    b, ac, fe = _stack()
    try:
        results = {}
        lock = threading.Lock()

        def client(i):
            status, doc, _ = _post_image(fe.url, i, priority=("interactive", "batch")[i % 2])
            with lock:
                results[i] = (status, doc["logits"])

        threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert results == {i: (200, [float(i)]) for i in range(16)}
    finally:
        fe.stop()
        b.stop()


# ---------------------------------------------------------------------------
# per-request telemetry: X-Request-Id, /metrics, /varz, trace correlation
# ---------------------------------------------------------------------------


def test_request_id_round_trip():
    """Every /predict response carries X-Request-Id: minted monotonic ids by
    default, a client-supplied id echoed back verbatim, and the header rides
    error responses too (a 429/5xx is exactly when you want the id)."""
    b, ac, fe = _stack()
    try:
        _, _, h1 = _post_image(fe.url, 1)
        _, _, h2 = _post_image(fe.url, 2)
        rid1, rid2 = int(h1["X-Request-Id"]), int(h2["X-Request-Id"])
        assert rid2 > rid1 > 0  # minted, process-monotonic
        # the body carries it too (clients that drop headers still get it)
        status, doc, h3 = _post_image(fe.url, 3)
        assert doc["request_id"] == h3["X-Request-Id"]
        # client-supplied correlation id is echoed verbatim
        img = np.full((4, 4, 3), 4.0, np.float32).tolist()
        status, doc, hdrs = _request(
            fe.url + "/predict", data=json.dumps({"image": img}).encode(),
            headers={"Content-Type": "application/json", "X-Request-Id": "client-abc-7"},
        )
        assert status == 200 and hdrs["X-Request-Id"] == "client-abc-7"
        assert doc["request_id"] == "client-abc-7"
        # errors carry the id as well (unknown class -> 400)
        status, doc, hdrs = _post_image(fe.url, 5, priority="platinum")
        assert status == 400 and hdrs.get("X-Request-Id")
    finally:
        fe.stop()
        b.stop()


def test_metrics_and_varz_scrape_surface():
    """GET /metrics returns Prometheus text exposition with per-class
    latency bucket + quantile lines; GET /varz the JSON registry snapshot
    (quantile columns included) plus admission state."""
    b, ac, fe = _stack()
    try:
        assert _post_image(fe.url, 1, priority="batch")[0] == 200
        req = urllib.request.Request(fe.url + "/metrics")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert "# TYPE serve_latency_seconds histogram" in text
        assert 'serve_latency_seconds_bucket{class="batch",le="+Inf"}' in text
        assert 'serve_latency_seconds{class="batch",quantile="0.99"}' in text
        assert 'serve_requests{class="batch"}' in text
        status, varz, _ = _request(fe.url + "/varz")
        assert status == 200
        assert varz["metrics"]["serve.latency_seconds.batch.count"] >= 1
        assert "serve.latency_seconds.batch.p99" in varz["metrics"]
        assert varz["metrics"]["serve.latency_seconds.batch.min"] > 0
        assert varz["admission"]["breaker"] == "closed"
        # device-telemetry surfaces ride /varz too: build identity + the
        # per-executable compile/cost table (dict; empty for this host-double
        # engine, populated by any real warmed engine in this process)
        assert isinstance(varz["build_info"], dict)
        assert isinstance(varz["executables"], dict)
    finally:
        fe.stop()
        b.stop()


def test_metrics_build_info_family():
    """/metrics carries the build_info version-attribution family once the
    CLI stamps it (cli/serve.py run() does at startup)."""
    from yet_another_mobilenet_series_tpu.obs import device as obs_device

    get_registry().set_build_info(obs_device.build_info())
    b, ac, fe = _stack()
    try:
        with urllib.request.urlopen(fe.url + "/metrics", timeout=30) as r:
            text = r.read().decode()
        line = next(l for l in text.splitlines() if l.startswith("build_info{"))
        assert "git_sha=" in line and "jax_version=" in line and "platform=" in line
        assert line.endswith("} 1")
    finally:
        fe.stop()
        b.stop()


def test_profiler_capture_endpoints(tmp_path):
    """POST /profile/start|stop: 200 with the trace dir, 409 on double
    start/stop, xplane dump on disk for trace_ops, 404 when unconfigured."""
    from yet_another_mobilenet_series_tpu.obs import device as obs_device

    b, ac, _fe = _stack()
    _fe.stop()  # rebuild with a profiler attached (same admission stack)
    cap = obs_device.ProfilerCapture(str(tmp_path / "trace"))
    fe = Frontend(ac, port=0, profiler=cap).start()
    try:
        status, body, _ = _request(fe.url + "/profile/start", data=b"", method="POST")
        assert status == 200 and body["ok"] and body["trace_dir"].endswith("trace")
        status, body, _ = _request(fe.url + "/profile/start", data=b"", method="POST")
        assert status == 409 and body["error"] == "profiler_state"
        # capture real serving traffic inside the window
        assert _post_image(fe.url, 3)[0] == 200
        status, body, _ = _request(fe.url + "/profile/stop", data=b"", method="POST")
        assert status == 200 and body["captured_s"] >= 0
        assert list((tmp_path / "trace").rglob("*.xplane.pb"))
        status, body, _ = _request(fe.url + "/profile/stop", data=b"", method="POST")
        assert status == 409
    finally:
        fe.stop()
        b.stop()
    # no profiler configured -> 404, never a crash
    b2, ac2, fe2 = _stack()
    try:
        status, body, _ = _request(fe2.url + "/profile/start", data=b"", method="POST")
        assert status == 404
    finally:
        fe2.stop()
        b2.stop()


def test_quantile_deadline_predictor():
    """predictor="quantile": once the class histogram has data, the wait
    prediction is the configured latency quantile (tail-aware) and feeds
    reject-on-arrival exactly like the EWMA mode."""
    class _Slow(_EchoEngine):
        def predict_async(self, images):
            time.sleep(0.05)
            return super().predict_async(images)

    get_registry().reset()  # the class histogram must start empty here
    b = PipelinedBatcher(_Slow(), max_batch=1, max_wait_ms=1.0,
                         queue_depth=64, drain_timeout_s=2.0).start()
    ac = AdmissionController(b, predictor="quantile", predictor_quantile=0.95)
    try:
        assert ac.predicted_wait_s("interactive") == 0.0  # no data yet
        fut = ac.submit(np.zeros((4, 4, 3), np.float32))
        fut.result(timeout=30)
        wait = ac.predicted_wait_s("interactive")
        assert wait > 0.01  # learned the ~50 ms tail from the histogram
        from yet_another_mobilenet_series_tpu.serve.admission import DeadlineUnmeetable
        with pytest.raises(DeadlineUnmeetable):
            ac.submit(np.zeros((4, 4, 3), np.float32), deadline_ms=1.0)
        assert ac.state()["predictor"] == "quantile"
    finally:
        b.stop()
    with pytest.raises(ValueError, match="predictor"):
        AdmissionController(b, predictor="p99ish")


def test_trace_correlates_one_request_across_threads():
    """The tentpole invariant, in-process: one request id appears in async
    (b/e) AND flow (s/t/f) events emitted from at least two distinct
    threads — handler, collect, completion — so Perfetto renders the
    request as one correlated waterfall."""
    from yet_another_mobilenet_series_tpu.obs import trace as obs_trace

    prev = obs_trace.get_tracer()
    tr = obs_trace.configure(enabled=True, ring_size=4096)
    try:
        b, ac, fe = _stack()
        try:
            status, _, hdrs = _post_image(fe.url, 3)
            assert status == 200
            rid = int(hdrs["X-Request-Id"])
        finally:
            fe.stop()
            b.stop()
        evts = [e for e in tr.to_chrome_trace()["traceEvents"] if e.get("id") == rid]
        phases = {e["ph"] for e in evts}
        assert {"b", "e"} <= phases, phases  # async waterfall edges
        assert {"s", "f"} <= phases, phases  # flow arrows
        assert len({e["tid"] for e in evts}) >= 2  # across threads
        names = {e["name"] for e in evts}
        assert {"serve/request", "serve/queued", "serve/inflight", "serve/req"} <= names
        # the envelope records the outcome
        env_end = next(e for e in evts if e["ph"] == "e" and e["name"] == "serve/request")
        assert env_end["args"]["outcome"] == "completed"
    finally:
        obs_trace._TRACER = prev


# ---------------------------------------------------------------------------
# the full front door: cli/serve.py --listen + SIGTERM drain (subprocess)
# ---------------------------------------------------------------------------

_LISTEN_DRIVER = """
import os, sys
os.environ["TF_CPP_MIN_LOG_LEVEL"] = "2"
from yet_another_mobilenet_series_tpu.cli.serve import main
main(sys.argv[1:])
"""


def test_cli_listen_end_to_end_sigterm_drain(tmp_path):
    """cli/serve.py --listen against a real exported bundle: HTTP predict
    with priority + deadline headers, /healthz with breaker/queue state,
    then SIGTERM -> graceful drain within serve.drain_timeout_s."""
    import jax

    from yet_another_mobilenet_series_tpu.config import ModelConfig
    from yet_another_mobilenet_series_tpu.models import get_model
    from yet_another_mobilenet_series_tpu.serve.export import export_bundle

    net = get_model(
        ModelConfig(arch="mobilenet_v2", num_classes=4, dropout=0.0,
                    block_specs=[{"t": 2, "c": 8, "n": 1, "s": 2}]),
        image_size=24,
    )
    params, state = net.init(jax.random.PRNGKey(0))
    bundle_dir = str(tmp_path / "bundle")
    export_bundle(net, params, state, bundle_dir)

    log_dir = str(tmp_path / "srv")
    proc = subprocess.Popen(
        [sys.executable, "-c", _LISTEN_DRIVER, "--listen",
         f"serve.bundle={bundle_dir}", "serve.buckets=[1,4]", "data.image_size=24",
         "serve.drain_timeout_s=10", "obs.trace=true", f"train.log_dir={log_dir}"],
        env=dict(os.environ, PYTHONPATH=REPO),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        addr_path = os.path.join(log_dir, "listen_addr.json")
        deadline = time.time() + 120
        while not os.path.exists(addr_path):
            assert proc.poll() is None, f"server died early:\n{proc.stdout.read()[-2000:]}"
            assert time.time() < deadline, "server never bound"
            time.sleep(0.2)
        addr = json.loads(open(addr_path).read())
        base = f"http://{addr['host']}:{addr['port']}"

        status, doc, hdrs = _post_image(base, 2, priority="interactive", deadline_ms=30000)
        assert status == 200 and len(doc["logits"]) == 4
        request_id = int(hdrs["X-Request-Id"])
        status, health, _ = _request(base + "/healthz")
        assert status == 200 and health["breaker"] == "closed"
        assert health["classes"]["interactive"]["quota"] >= 1
        # the live scrape surface: Prometheus exposition with per-class
        # latency bucket + quantile lines (the acceptance criterion)
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            metrics_text = r.read().decode()
        assert 'serve_latency_seconds_bucket{class="interactive",le="+Inf"}' in metrics_text
        assert 'serve_latency_seconds{class="interactive",quantile="0.99"}' in metrics_text
        status, varz, _ = _request(base + "/varz")
        assert status == 200 and varz["metrics"]["serve.latency_seconds.interactive.count"] >= 1

        proc.send_signal(signal.SIGTERM)
        t0 = time.time()
        rc = proc.wait(timeout=30)
        assert rc == 0
        assert time.time() - t0 < 15  # drained inside the configured bound
        out = proc.stdout.read()
        assert "drained in" in out and "clean" in out
        # obs artifacts landed, with the front-door counters in them
        snap = json.loads(open(os.path.join(log_dir, "obs_registry.json")).read())
        assert snap["serve.requests.interactive"] >= 1
        assert snap["serve.http_requests"] >= 1
        assert snap["serve.breaker_state"] == 0
        assert snap["serve.latency_seconds.interactive.p99"] > 0
        # the trace correlates the served request's id across threads:
        # async (b/e) waterfall edges AND flow (s/t/f) arrows from at least
        # two distinct tids (HTTP handler / collect / completion)
        trace = json.loads(open(os.path.join(log_dir, "obs_trace.json")).read())
        corr = [e for e in trace["traceEvents"] if e.get("id") == request_id]
        phases = {e["ph"] for e in corr}
        assert {"b", "e"} <= phases and ({"s", "t", "f"} & phases), phases
        assert len({e["tid"] for e in corr}) >= 2
        assert {"serve/request", "serve/queued", "serve/inflight"} <= {e["name"] for e in corr}
        thread_rows = {e["args"]["name"] for e in trace["traceEvents"]
                       if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"serve-collect", "serve-complete", "serve-http"} <= thread_rows
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
