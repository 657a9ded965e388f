"""Serving-plane tests: Envoy RLS v3 gRPC + Kuadrant split + HTTP API +
limits-file hot reload, over real sockets.

Mirrors the reference's service tests (envoy_rls/server.rs:302-772,
kuadrant_service.rs:187-649, http_api/server.rs:332-648) but through live
servers rather than direct method invocation — the batcher and event loop
are part of what's under test here.
"""

import asyncio
import json
import socket
import time

import grpc
import pytest

from limitador_tpu import Limit, RateLimiter
from limitador_tpu.observability import PrometheusMetrics
from limitador_tpu.server.http_api import run_http_server
from limitador_tpu.server.proto import rls_pb2
from limitador_tpu.server.rls import (
    RATE_LIMIT_HEADERS_DRAFT03,
    serve_rls,
)
from limitador_tpu.storage.in_memory import InMemoryStorage


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def make_request(domain="test_namespace", entries=None, hits_addend=0):
    req = rls_pb2.RateLimitRequest(domain=domain, hits_addend=hits_addend)
    d = req.descriptors.add()
    for k, v in (entries or {}).items():
        e = d.entries.add()
        e.key = k
        e.value = v
    return req


def grpc_call(port, method, request, timeout=5.0):
    with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
        fn = channel.unary_unary(
            method,
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=rls_pb2.RateLimitResponse.FromString,
        )
        return fn(request, timeout=timeout)


ENVOY_METHOD = "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit"
KUADRANT_CHECK = "/kuadrant.service.ratelimit.v1.RateLimitService/CheckRateLimit"
KUADRANT_REPORT = "/kuadrant.service.ratelimit.v1.RateLimitService/Report"


@pytest.fixture
def rls_server():
    """A live RLS gRPC server over a limiter with one conditioned limit."""
    limiter = RateLimiter(InMemoryStorage())
    limiter.add_limit(
        Limit(
            "test_namespace", 3, 60,
            ["descriptors[0]['req.method'] == 'GET'"], ["descriptors[0].user"],
            name="per-user-get",
        )
    )
    metrics = PrometheusMetrics(use_limit_name_label=True)
    port = free_port()
    loop = asyncio.new_event_loop()
    server = loop.run_until_complete(
        serve_rls(
            limiter, f"127.0.0.1:{port}", metrics, RATE_LIMIT_HEADERS_DRAFT03
        )
    )
    import threading

    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    yield port, limiter, metrics
    asyncio.run_coroutine_threadsafe(server.stop(grace=None), loop).result()
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=2)


class TestEnvoyRls:
    def test_should_rate_limit_ok_then_over_limit(self, rls_server):
        port, _limiter, _metrics = rls_server
        entries = {"req.method": "GET", "user": "alice"}
        for _ in range(3):
            resp = grpc_call(port, ENVOY_METHOD, make_request(entries=entries))
            assert resp.overall_code == rls_pb2.RateLimitResponse.OK
        resp = grpc_call(port, ENVOY_METHOD, make_request(entries=entries))
        assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT

    def test_draft03_headers_present(self, rls_server):
        port, *_ = rls_server
        resp = grpc_call(
            port, ENVOY_METHOD,
            make_request(entries={"req.method": "GET", "user": "bob"}),
        )
        headers = {h.key: h.value for h in resp.response_headers_to_add}
        assert headers["X-RateLimit-Limit"].startswith("3, 3;w=60")
        assert headers["X-RateLimit-Remaining"] == "2"

    def test_empty_domain_returns_unknown(self, rls_server):
        port, *_ = rls_server
        resp = grpc_call(port, ENVOY_METHOD, make_request(domain=""))
        assert resp.overall_code == rls_pb2.RateLimitResponse.UNKNOWN

    def test_hits_addend_defaults_to_one_and_applies(self, rls_server):
        port, *_ = rls_server
        entries = {"req.method": "GET", "user": "carol"}
        resp = grpc_call(
            port, ENVOY_METHOD, make_request(entries=entries, hits_addend=3)
        )
        assert resp.overall_code == rls_pb2.RateLimitResponse.OK
        resp = grpc_call(port, ENVOY_METHOD, make_request(entries=entries))
        assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT

    def test_unmatched_descriptor_is_ok(self, rls_server):
        port, *_ = rls_server
        resp = grpc_call(
            port, ENVOY_METHOD,
            make_request(entries={"req.method": "POST", "user": "dave"}),
        )
        assert resp.overall_code == rls_pb2.RateLimitResponse.OK

    def test_metrics_counted(self, rls_server):
        port, _limiter, metrics = rls_server
        entries = {"req.method": "GET", "user": "eve"}
        for _ in range(4):
            grpc_call(port, ENVOY_METHOD, make_request(entries=entries))
        text = metrics.render().decode()
        assert (
            'authorized_calls_total{limitador_namespace="test_namespace"} 3.0'
            in text
        )
        assert 'limited_calls_total' in text
        assert 'limitador_limit_name="per-user-get"' in text


class TestKuadrantService:
    def test_check_is_read_only(self, rls_server):
        port, *_ = rls_server
        entries = {"req.method": "GET", "user": "frank"}
        # 10 read-only checks never consume quota
        for _ in range(10):
            resp = grpc_call(port, KUADRANT_CHECK, make_request(entries=entries))
            assert resp.overall_code == rls_pb2.RateLimitResponse.OK

    def test_report_updates(self, rls_server):
        port, *_ = rls_server
        entries = {"req.method": "GET", "user": "gina"}
        for _ in range(3):
            resp = grpc_call(
                port, KUADRANT_REPORT, make_request(entries=entries)
            )
            assert resp.overall_code == rls_pb2.RateLimitResponse.OK
        resp = grpc_call(port, KUADRANT_CHECK, make_request(entries=entries))
        assert resp.overall_code == rls_pb2.RateLimitResponse.OVER_LIMIT


@pytest.fixture
def http_server():
    limiter = RateLimiter(InMemoryStorage())
    limiter.add_limit(
        Limit(
            "test_namespace", 2, 60,
            ["descriptors[0]['req_method'] == 'GET'"],
            ["descriptors[0].user"],
        )
    )
    metrics = PrometheusMetrics()
    port = free_port()
    loop = asyncio.new_event_loop()
    runner = loop.run_until_complete(
        run_http_server(
            limiter, "127.0.0.1", port, metrics,
            {"limits_file_version": 1},
        )
    )
    import threading

    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    yield port, limiter
    asyncio.run_coroutine_threadsafe(runner.cleanup(), loop).result()
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=2)


class TestHttpApi:

    def _post(self, port, path, body):
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req) as resp:
                return resp.status, dict(resp.headers)
        except urllib.error.HTTPError as exc:
            return exc.code, dict(exc.headers)

    def _get(self, port, path):
        import urllib.request

        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as resp:
            return resp.status, resp.read()

    def test_status(self, http_server):
        port, _ = http_server
        status, body = self._get(port, "/status")
        assert status == 200
        assert json.loads(body)["limits_file_version"] == 1

    def test_limits_endpoint(self, http_server):
        port, _ = http_server
        status, body = self._get(port, "/limits/test_namespace")
        assert status == 200
        limits = json.loads(body)
        assert len(limits) == 1
        assert limits[0]["max_value"] == 2

    def test_check_and_report_flow(self, http_server):
        port, _ = http_server
        body = {
            "namespace": "test_namespace",
            "values": {"req_method": "GET", "user": "u1"},
            "delta": 1,
            "response_headers": "DRAFT_VERSION_03",
        }
        st, headers = self._post(port, "/check_and_report", body)
        assert st == 200
        assert headers["X-RateLimit-Remaining"] == "1"
        st, _ = self._post(port, "/check_and_report", body)
        assert st == 200
        st, headers = self._post(port, "/check_and_report", body)
        assert st == 429
        assert headers["X-RateLimit-Remaining"] == "0"

    def test_check_and_report_split(self, http_server):
        port, _ = http_server
        body = {
            "namespace": "test_namespace",
            "values": {"req_method": "GET", "user": "u2"},
            "delta": 1,
        }
        assert self._post(port, "/check", body)[0] == 200
        assert self._post(port, "/report", body)[0] == 200
        assert self._post(port, "/report", body)[0] == 200
        assert self._post(port, "/check", body)[0] == 429

    def test_counters_endpoint(self, http_server):
        port, _ = http_server
        self._post(
            port, "/report",
            {
                "namespace": "test_namespace",
                "values": {"req_method": "GET", "user": "u3"},
                "delta": 1,
            },
        )
        status, body = self._get(port, "/counters/test_namespace")
        assert status == 200
        counters = json.loads(body)
        assert len(counters) == 1
        assert counters[0]["remaining"] == 1
        assert counters[0]["set_variables"] == {"descriptors[0].user": "u3"}

    def test_bad_request(self, http_server):
        port, _ = http_server
        st, _ = self._post(port, "/check", {"nope": 1})
        assert st == 400

    def test_metrics_endpoint(self, http_server):
        port, _ = http_server
        status, body = self._get(port, "/metrics")
        assert status == 200
        assert b"limitador_up 1.0" in body


class TestLimitsFile:
    def test_load_validate_and_hot_reload(self, tmp_path):
        from limitador_tpu.server.limits_file import (
            LimitsFileWatcher,
            load_limits_file,
        )

        path = tmp_path / "limits.yaml"
        path.write_text(
            "- namespace: ns\n  max_value: 5\n  seconds: 60\n"
            "  conditions:\n  - \"x == '1'\"\n  variables:\n  - user\n"
        )
        limits = load_limits_file(str(path))
        assert len(limits) == 1 and limits[0].max_value == 5

        seen = []
        watcher = LimitsFileWatcher(
            str(path), lambda ls: seen.append(ls), poll_interval=0.05
        )
        watcher.start()
        time.sleep(0.1)
        path.write_text("- namespace: ns\n  max_value: 9\n  seconds: 60\n")
        deadline = time.time() + 10  # exits on first sighting; generous
        # bound absorbs scheduler stalls under full-suite load
        while not seen and time.time() < deadline:
            time.sleep(0.05)
        watcher.stop()
        assert seen and seen[0][0].max_value == 9

    def test_invalid_file_counts_errors(self, tmp_path):
        from limitador_tpu.server.limits_file import (
            LimitsFileError,
            LimitsFileWatcher,
            load_limits_file,
        )

        path = tmp_path / "limits.yaml"
        path.write_text("- namespace: ns\n  max_value: 5\n  seconds: 60\n")
        load_limits_file(str(path))

        errors = []
        watcher = LimitsFileWatcher(
            str(path), lambda ls: None, on_error=errors.append,
            poll_interval=0.05,
        )
        watcher.start()
        time.sleep(0.1)
        path.write_text("- namespace: ns\n  seconds: [broken\n")
        deadline = time.time() + 3
        while not errors and time.time() < deadline:
            time.sleep(0.05)
        watcher.stop()
        assert errors and watcher.errors == 1

        with pytest.raises(LimitsFileError):
            load_limits_file(str(tmp_path / "missing.yaml"))


class TestReviewRegressions:
    def test_negative_delta_rejected(self):
        from limitador_tpu.server.http_api import _Api

        with pytest.raises(ValueError):
            _Api._parse_info({"namespace": "ns", "delta": -5})

    def test_kuadrant_check_uses_delta_one(self, rls_server):
        # remaining 3; hits_addend=5 on Check must still be OK (delta 1)
        port, *_ = rls_server
        resp = grpc_call(
            port, KUADRANT_CHECK,
            make_request(entries={"req.method": "GET", "user": "hank"},
                         hits_addend=5),
        )
        assert resp.overall_code == rls_pb2.RateLimitResponse.OK

    def test_kuadrant_metric_split(self, rls_server):
        port, _limiter, metrics = rls_server
        entries = {"req.method": "GET", "user": "iris"}
        grpc_call(port, KUADRANT_CHECK, make_request(entries=entries))
        grpc_call(port, KUADRANT_REPORT,
                  make_request(entries=entries, hits_addend=2))
        text = metrics.render().decode()
        # Check counts the call; Report counts only hits.
        assert 'authorized_calls_total{limitador_namespace="test_namespace"} 1.0' in text
        assert 'authorized_hits_total{limitador_namespace="test_namespace"} 2.0' in text


class TestObservabilityExtras:
    def test_custom_metric_labels(self):
        from limitador_tpu.core.cel import Context as CelContext

        metrics = PrometheusMetrics(
            metric_labels="{'tenant': descriptors[0].tenant}"
        )
        ctx = CelContext()
        ctx.list_binding("descriptors", [{"tenant": "acme", "u": "x"}])
        metrics.incr_authorized_calls("ns", ctx=ctx)
        metrics.incr_limited_calls("ns", None, ctx=ctx)
        # missing tenant -> empty label, never an error
        ctx2 = CelContext()
        ctx2.list_binding("descriptors", [{"u": "y"}])
        metrics.incr_authorized_calls("ns", ctx=ctx2)
        text = metrics.render().decode()
        assert 'authorized_calls_total{limitador_namespace="ns",tenant="acme"} 1.0' in text
        assert 'authorized_calls_total{limitador_namespace="ns",tenant=""} 1.0' in text
        assert 'limited_calls_total{limitador_namespace="ns",tenant="acme"} 1.0' in text

    def test_metric_labels_reject_non_map(self):
        with pytest.raises(ValueError):
            PrometheusMetrics(metric_labels="descriptors[0].x")

    def test_http_request_id_echo(self, http_server):
        import urllib.request

        port, _ = http_server
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/status",
            headers={"x-request-id": "abc-123"},
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.headers["x-request-id"] == "abc-123"
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/status") as resp:
            assert len(resp.headers["x-request-id"]) == 32  # generated

    def test_grpc_request_id_metadata(self, rls_server):
        import grpc as grpc_mod

        port, *_ = rls_server
        with grpc_mod.insecure_channel(f"127.0.0.1:{port}") as channel:
            fn = channel.unary_unary(
                ENVOY_METHOD,
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=rls_pb2.RateLimitResponse.FromString,
            )
            call = fn.with_call(
                make_request(entries={"req.method": "GET", "user": "rid"}),
                metadata=(("x-request-id", "rid-42"),),
                timeout=5,
            )
            initial = dict(call[1].initial_metadata())
            assert initial.get("x-request-id") == "rid-42"


def test_api_spec_served():
    """/api/spec serves an OpenAPI doc covering every endpoint
    (http_api/server.rs:282-330)."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from limitador_tpu import RateLimiter
    from limitador_tpu.server.http_api import make_http_app

    async def main():
        app = make_http_app(RateLimiter(), None, {})
        client = TestClient(TestServer(app))
        await client.start_server()
        resp = await client.get("/api/spec")
        spec = await resp.json()
        await client.close()
        return resp.status, spec

    loop = asyncio.new_event_loop()
    try:
        status, spec = loop.run_until_complete(main())
    finally:
        loop.close()
    assert status == 200
    assert spec["openapi"].startswith("3.")
    for path in ("/status", "/metrics", "/limits/{namespace}",
                 "/counters/{namespace}", "/check", "/report",
                 "/check_and_report", "/debug/stats", "/debug/profile"):
        assert path in spec["paths"], path
    assert set(spec["components"]["schemas"]) == {
        "Limit", "Counter", "CheckAndReportInfo", "ProfileAction"
    }


def test_metric_labels_reload(tmp_path):
    """Label value expressions hot-swap; new names are rejected (prometheus
    label names are fixed per process)."""
    from limitador_tpu import Context
    from limitador_tpu.observability.metrics import PrometheusMetrics

    metrics = PrometheusMetrics(
        metric_labels="{'tenant': descriptors[0].t}"
    )
    ctx = Context()
    ctx.list_binding("descriptors", [{"t": "acme", "other": "x"}])
    assert metrics.custom_labels(ctx) == ["acme"]
    metrics.reload_labels("{'tenant': descriptors[0].other}")
    assert metrics.custom_labels(ctx) == ["x"]
    import pytest as _pytest

    with _pytest.raises(ValueError):
        metrics.reload_labels("{'brand_new': descriptors[0].t}")


def test_metric_labels_file_watcher(tmp_path):
    """Editing the labels file takes effect without restart (the watcher
    path used by the server's --metric-labels-file)."""
    import time

    from limitador_tpu import Context
    from limitador_tpu.observability.metrics import PrometheusMetrics
    from limitador_tpu.server.limits_file import LimitsFileWatcher

    path = tmp_path / "labels.cel"
    path.write_text("{'tenant': descriptors[0].t}")
    metrics = PrometheusMetrics(metric_labels=path.read_text())

    def _load(p):
        with open(p) as f:
            return f.read().strip()

    watcher = LimitsFileWatcher(
        str(path),
        lambda content: metrics.reload_labels(content),
        poll_interval=0.05,
        loader=_load,
    )
    watcher.start()
    try:
        ctx = Context()
        ctx.list_binding("descriptors", [{"t": "acme", "other": "x"}])
        assert metrics.custom_labels(ctx) == ["acme"]
        time.sleep(0.1)
        path.write_text("{'tenant': descriptors[0].other}")
        deadline = time.time() + 5
        while metrics.custom_labels(ctx) != ["x"]:
            assert time.time() < deadline, "labels never reloaded"
            time.sleep(0.05)
    finally:
        watcher.stop()


def test_cached_cli_knobs_wire_through(tmp_path):
    """The reference's redis_cached tuning flags (--batch-size,
    --flush-period, --max-cached, --response-timeout;
    main.rs:651-690) reach the cached storage and its authority."""
    from limitador_tpu.server.__main__ import build_limiter, build_parser

    args = build_parser().parse_args([
        "nonexistent.yaml", "cached",
        "--disk-path", str(tmp_path / "c.db"),
        "--batch-size", "7",
        "--flush-period", "250",
        "--max-cached", "123",
    ])
    limiter = build_limiter(args)
    storage = limiter.storage.counters
    assert storage.batch_size == 7
    assert storage.flush_period == 0.25  # flag is ms, like the reference
    assert storage.max_cached == 123
    # Defaults mirror redis/mod.rs:10-13 (periods/timeouts in ms).
    d = build_parser().parse_args(["x.yaml", "cached"])
    assert d.batch_size == 100
    assert d.flush_period == 1000
    assert d.max_cached == 10000
    assert d.response_timeout == 350
