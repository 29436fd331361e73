"""Event-loop serving frontend tests (api/aio_http.py): HTTP/1.1
framing (keep-alive, pipelining, Content-Length edge cases), transport
parity with the threaded fallback, the future-based micro-batch
handoff, and the serving-observability satellites."""

import concurrent.futures
import http.client
import json
import socket
import threading
import time

import pytest

from predictionio_tpu.api.aio_http import (
    AsyncJsonHTTPServer,
    make_http_server,
)
from predictionio_tpu.api.http import JsonHTTPServer

from tests import fake_engine as fe
from tests.test_engine_server import (
    after_submits,
    make_engine,
    train_instance,
)


def _echo_handler(method, path, query, body, form=None):
    return 200, {
        "method": method,
        "path": path,
        "query": query,
        "body": (body or b"").decode("utf-8", "replace"),
        "form": form,
    }


@pytest.fixture(params=["async", "threaded"])
def echo_server(request):
    server = make_http_server(
        _echo_handler, "localhost", 0, "Echo", transport=request.param
    ).start()
    yield server, request.param
    server.shutdown()


def _recv_all(sock, timeout=10.0):
    sock.settimeout(timeout)
    data = b""
    try:
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
    except socket.timeout:
        pass
    return data


class TestFraming:
    def test_keep_alive_two_requests_one_connection(self, echo_server):
        """Two requests ride ONE persistent connection on both
        transports (http.client reuses the socket unless the server
        closes it)."""
        server, _ = echo_server
        conn = http.client.HTTPConnection("localhost", server.port)
        try:
            conn.request("GET", "/first?a=1")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["path"] == "/first"
            first_sock = conn.sock
            assert first_sock is not None
            conn.request(
                "POST", "/second", b'{"x": 2}',
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["body"] == '{"x": 2}'
            # same socket object: the connection was never torn down
            assert conn.sock is first_sock
        finally:
            conn.close()

    def test_pipelined_requests_ordered_responses(self, echo_server):
        """Both requests sent before any response is read; both answers
        come back, in request order."""
        server, _ = echo_server
        raw = socket.create_connection(("localhost", server.port))
        try:
            raw.sendall(
                b"GET /one HTTP/1.1\r\nHost: t\r\n\r\n"
                b"GET /two HTTP/1.1\r\nHost: t\r\nConnection: close\r\n\r\n"
            )
            data = _recv_all(raw)
        finally:
            raw.close()
        assert data.count(b"HTTP/1.1 200") == 2
        assert data.index(b"/one") < data.index(b"/two")

    def test_garbage_content_length_is_400(self, echo_server):
        server, _ = echo_server
        raw = socket.create_connection(("localhost", server.port))
        try:
            raw.sendall(
                b"POST /x HTTP/1.1\r\nHost: t\r\n"
                b"Content-Length: not-a-number\r\n\r\n"
            )
            data = _recv_all(raw)
        finally:
            raw.close()
        assert data.startswith(b"HTTP/1.1 400")

    def test_oversized_content_length_is_413_without_reading(
        self, echo_server
    ):
        """A hostile Content-Length is refused BEFORE any body bytes are
        read or buffered."""
        server, _ = echo_server
        raw = socket.create_connection(("localhost", server.port))
        try:
            raw.sendall(
                b"POST /x HTTP/1.1\r\nHost: t\r\n"
                b"Content-Length: 999999999999\r\n\r\n"
            )
            data = _recv_all(raw)
        finally:
            raw.close()
        assert data.startswith(b"HTTP/1.1 413")

    def test_chunked_transfer_refused_501(self, echo_server):
        server, _ = echo_server
        raw = socket.create_connection(("localhost", server.port))
        try:
            raw.sendall(
                b"POST /x HTTP/1.1\r\nHost: t\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n"
                b"4\r\nbody\r\n0\r\n\r\n"
            )
            data = _recv_all(raw)
        finally:
            raw.close()
        assert data.startswith(b"HTTP/1.1 501")

    def test_http_1_0_closes_after_response(self, echo_server):
        server, _ = echo_server
        raw = socket.create_connection(("localhost", server.port))
        try:
            raw.sendall(b"GET /legacy HTTP/1.0\r\n\r\n")
            data = _recv_all(raw)
        finally:
            raw.close()
        # the server answered, then closed (recv_all saw EOF, not timeout)
        assert data.startswith(b"HTTP/1.1 200")


class TestAsyncTransportSpecifics:
    def test_future_result_is_awaited_not_blocked(self):
        """A handler returning a concurrent Future resolves when the
        future does — no thread parks in between, and slow futures do
        not block other connections on the loop."""
        pool = concurrent.futures.ThreadPoolExecutor(2)
        release = threading.Event()

        def handler(method, path, query, body, form=None):
            if path == "/slow":
                def work():
                    release.wait(10.0)
                    return 200, {"slow": True}
                return pool.submit(work)
            return 200, {"fast": True}

        server = AsyncJsonHTTPServer(handler, "localhost", 0, "T").start()
        try:
            slow_conn = http.client.HTTPConnection("localhost", server.port)
            slow_conn.request("GET", "/slow")
            # while /slow is pending, the loop must still answer /fast
            fast_conn = http.client.HTTPConnection("localhost", server.port)
            fast_conn.request("GET", "/fast")
            resp = fast_conn.getresponse()
            assert json.loads(resp.read()) == {"fast": True}
            fast_conn.close()
            release.set()
            resp = slow_conn.getresponse()
            assert json.loads(resp.read()) == {"slow": True}
            slow_conn.close()
        finally:
            server.shutdown()
            pool.shutdown(wait=False)

    def test_handler_exception_is_500(self):
        def handler(method, path, query, body, form=None):
            raise RuntimeError("boom")

        server = AsyncJsonHTTPServer(handler, "localhost", 0, "T").start()
        try:
            conn = http.client.HTTPConnection("localhost", server.port)
            conn.request("GET", "/x")
            resp = conn.getresponse()
            assert resp.status == 500
            assert json.loads(resp.read())["message"] == "boom"
            conn.close()
        finally:
            server.shutdown()

    def test_failed_future_is_500(self):
        pool = concurrent.futures.ThreadPoolExecutor(1)

        def handler(method, path, query, body, form=None):
            def work():
                raise ValueError("deferred boom")
            return pool.submit(work)

        server = AsyncJsonHTTPServer(handler, "localhost", 0, "T").start()
        try:
            conn = http.client.HTTPConnection("localhost", server.port)
            conn.request("GET", "/x")
            resp = conn.getresponse()
            assert resp.status == 500
            assert "deferred boom" in json.loads(resp.read())["message"]
            conn.close()
        finally:
            server.shutdown()
            pool.shutdown(wait=False)

    def test_pipelining_client_abort_releases_connection(self):
        """A client that pipelines many requests and disconnects before
        reading the responses must not park the connection task forever
        on the bounded response queue (the writer drains to _CLOSE in
        discard mode) — the task, socket, and buffered responses are
        all released without a server shutdown."""
        server = AsyncJsonHTTPServer(
            _echo_handler, "localhost", 0, "T"
        ).start()
        try:
            raw = socket.create_connection(("localhost", server.port))
            # far more pipelined requests than PIPELINE_DEPTH slots
            raw.sendall(
                b"".join(
                    b"GET /r%d HTTP/1.1\r\nHost: t\r\n\r\n" % j
                    for j in range(64)
                )
            )
            raw.recv(128)  # read a fragment, then abort
            raw.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                b"\x01\x00\x00\x00\x00\x00\x00\x00",  # RST on close
            )
            raw.close()
            deadline = time.time() + 5
            while server._conn_tasks and time.time() < deadline:
                time.sleep(0.05)
            assert not server._conn_tasks  # connection fully released
            # and the server still answers fresh connections
            conn = http.client.HTTPConnection("localhost", server.port)
            conn.request("GET", "/alive")
            assert conn.getresponse().status == 200
            conn.close()
        finally:
            server.shutdown()

    def test_bind_conflict_raises_oserror(self):
        s1 = AsyncJsonHTTPServer(_echo_handler, "localhost", 0, "T1")
        old = JsonHTTPServer.BIND_RETRIES
        JsonHTTPServer.BIND_RETRIES = 1  # shared retry tunable
        try:
            with pytest.raises(OSError):
                AsyncJsonHTTPServer(
                    _echo_handler, "localhost", s1.port, "T2"
                )
        finally:
            JsonHTTPServer.BIND_RETRIES = old
            s1.shutdown()

    def test_shutdown_idempotent_and_releases_port(self):
        server = AsyncJsonHTTPServer(
            _echo_handler, "localhost", 0, "T"
        ).start()
        port = server.port
        server.shutdown()
        server.shutdown()  # idempotent
        # port is free again
        probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            probe.bind(("localhost", port))
        finally:
            probe.close()


class TestMicroBatchCoalescing:
    def test_32_clients_fill_device_batches(self, mem_storage):
        """The headline property: with in-flight queries held as queue
        entries (not parked threads), >=32 concurrent clients coalesce
        into multi-query device batches — batch_fill_mean must clear 1
        by a wide margin. What coalesces them is a serve slot that is
        held: the first batch stays in serve_batch until half the
        clients' first requests have queued behind it."""
        from predictionio_tpu.api.engine_server import (
            EngineServer,
            ServerConfig,
        )

        fe.reset_counters()
        train_instance(mem_storage)
        server = EngineServer(
            make_engine(),
            ServerConfig(port=0, max_batch=64, transport="async"),
            storage=mem_storage,
        ).start()
        queued_behind = after_submits(server.api._executor, 16)
        serve_batch = server.api.deployed.serve_batch

        def held(queries):
            assert queued_behind.wait(10.0)
            return serve_batch(queries)

        server.api.deployed.serve_batch = held
        try:
            def client(worker):
                conn = http.client.HTTPConnection("localhost", server.port)
                out = []
                try:
                    for j in range(3):
                        qx = worker * 10 + j
                        conn.request(
                            "POST", "/queries.json",
                            json.dumps({"qx": qx}),
                            {"Content-Type": "application/json"},
                        )
                        resp = conn.getresponse()
                        out.append((qx, resp.status, json.loads(resp.read())))
                finally:
                    conn.close()
                return out

            with concurrent.futures.ThreadPoolExecutor(32) as pool:
                chunks = list(pool.map(client, range(32)))
            for chunk in chunks:
                for qx, status, body in chunk:
                    assert status == 200
                    assert body["qx"] == qx
            stats = server.api._executor.stats()
            assert stats["queries"] == 96
            assert stats["batch_fill_mean"] > 1.0, stats
            # the histogram proves multi-query batches actually formed
            assert any(size > 1 for size in stats["batch_size_histogram"])
            # and status.json surfaces the same accounting
            _, status_json, _ = server.api.handle("GET", "/status.json")
            assert status_json["batchFillMean"] == pytest.approx(
                stats["batch_fill_mean"], rel=0.5
            )
            assert status_json["p50ServingSec"] > 0
            assert status_json["p99ServingSec"] >= status_json["p50ServingSec"]
        finally:
            server.shutdown()

    def test_threaded_fallback_serves_queries(self, mem_storage):
        """The threaded transport stays a complete fallback: same
        routes, same results, blocking submit path."""
        from predictionio_tpu.api.engine_server import (
            EngineServer,
            ServerConfig,
        )

        fe.reset_counters()
        train_instance(mem_storage)
        server = EngineServer(
            make_engine(),
            ServerConfig(port=0, transport="threaded"),
            storage=mem_storage,
        ).start()
        try:
            conn = http.client.HTTPConnection("localhost", server.port)
            conn.request(
                "POST", "/queries.json", json.dumps({"qx": 5}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["qx"] == 5
            conn.request("GET", "/status.json")
            resp = conn.getresponse()
            assert json.loads(resp.read())["requestCount"] == 1
            conn.close()
        finally:
            server.shutdown()


class TestSubmitNowait:
    def test_future_resolves_with_result(self):
        from predictionio_tpu.api.engine_server import _BatchingExecutor

        class Dep:
            def serve_batch(self, queries):
                return [q * 2 for q in queries]

        ex = _BatchingExecutor(max_batch=4)
        try:
            futs = [ex.submit_nowait(Dep(), i) for i in range(3)]
            assert [f.result(timeout=5) for f in futs] == [0, 2, 4]
        finally:
            ex.close()

    def test_future_carries_per_query_error(self):
        from predictionio_tpu.api.engine_server import _BatchingExecutor

        class PoisonDep:
            def serve_batch(self, queries):
                if any(q == 1 for q in queries):
                    raise ValueError("poison")
                return list(queries)

        dep = PoisonDep()
        ex = _BatchingExecutor(max_batch=8)
        try:
            futs = [ex.submit_nowait(dep, i) for i in range(4)]
            assert futs[0].result(timeout=5) == 0
            with pytest.raises(ValueError, match="poison"):
                futs[1].result(timeout=5)
            assert futs[2].result(timeout=5) == 2
            assert futs[3].result(timeout=5) == 3
        finally:
            ex.close()

    def test_cancelled_future_is_dropped_from_batch(self):
        from predictionio_tpu.api.engine_server import _BatchingExecutor

        served = []

        class Dep:
            def serve_batch(self, queries):
                served.extend(queries)
                return list(queries)

        gate = threading.Event()
        entered = threading.Event()

        class GateDep(Dep):
            def serve_batch(self, queries):
                entered.set()
                gate.wait(5.0)
                return super().serve_batch(queries)

        dep = GateDep()
        ex = _BatchingExecutor(max_batch=8)
        try:
            # "hold" goes out at once and keeps the one slot, so the
            # batch of "a" and "b" has not formed when "b" is cancelled
            hold = ex.submit_nowait(dep, "hold")
            assert entered.wait(5.0)
            first = ex.submit_nowait(dep, "a")
            doomed = ex.submit_nowait(dep, "b")
            assert doomed.cancel()  # client went away pre-batch
            gate.set()
            assert hold.result(timeout=5) == "hold"
            assert first.result(timeout=5) == "a"
            deadline = time.time() + 5
            while "a" not in served and time.time() < deadline:
                time.sleep(0.01)
            assert "a" in served and "b" not in served
        finally:
            ex.close()

    def test_submit_blocking_wrapper_unchanged(self):
        from predictionio_tpu.api.engine_server import _BatchingExecutor

        class Dep:
            def serve_batch(self, queries):
                return [q + 1 for q in queries]

        ex = _BatchingExecutor(max_batch=4)
        try:
            assert ex.submit(Dep(), 41) == 42
        finally:
            ex.close()
