"""Keep-alive + cp-mux/1 conformance suites for the shared HTTP
server (utils/http.py).

Keep-alive: multiple requests per connection, the opt-outs
(``Connection: close``, HTTP/1.0), idle/cap reaping, the streaming
close-delimited contract, and no leaked handler state on abrupt
client disconnects. Every server in the tree (control plane,
telemetry, inference, gateway, catalog emulator) sits on this.

cp-mux/1 (the fleet's multiplexed transport): negotiated upgrade +
HTTP/1.1 fallback, stream interleaving on one connection, per-stream
backpressure windows, CANCEL mid-DATA with handler cleanup, protocol
errors closing the connection, abort() failing all streams, and the
per-connection stream cap refusing (not killing) the excess stream.
"""
import asyncio
import http.client
import json
import socket

from containerpilot_tpu.utils.http import (
    FRAME_END,
    FRAME_HEADERS,
    FRAME_PING,
    FRAME_PONG,
    HTTPServer,
    MUX_PROTOCOL,
    MUX_UPGRADE_PATH,
    Response,
    StreamingResponse,
    encode_frame,
    read_frame,
)


async def _start_server(**attrs):
    server = HTTPServer()
    for key, value in attrs.items():
        setattr(server, key, value)

    async def ok(req):
        return Response(200, b"hello\n")

    async def echo(req):
        return Response(200, req.body, content_type="application/json")

    async def stream(_req):
        async def gen():
            yield b"data: 1\n\n"
            yield b"data: 2\n\n"

        return StreamingResponse(gen())

    server.route("GET", "/ok", ok)
    server.route("POST", "/echo", echo)
    server.route("GET", "/stream", stream)
    await server.start_tcp("127.0.0.1", 0)
    return server


def _recv_all(sock, timeout=5.0):
    """Read until EOF (or timeout, which fails the test loudly)."""
    sock.settimeout(timeout)
    chunks = []
    while True:
        chunk = sock.recv(65536)
        if not chunk:
            return b"".join(chunks)
        chunks.append(chunk)


def test_sequential_requests_reuse_one_connection(run):
    """N requests on one http.client connection: one accept, N
    responses, each advertising keep-alive."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.bound_port, timeout=5
            )
            out = []
            for _ in range(5):
                conn.request("GET", "/ok")
                resp = conn.getresponse()
                out.append(
                    (resp.status, resp.read(), resp.getheader("Connection"))
                )
            conn.close()
            return out

        out = await loop.run_in_executor(None, client)
        counters = (server.connections_accepted, server.requests_served)
        await server.stop()
        return out, counters

    out, (conns, reqs) = run(scenario(), timeout=30)
    assert out == [(200, b"hello\n", "keep-alive")] * 5
    assert conns == 1 and reqs == 5


def test_connection_close_header_is_honored(run):
    """A request carrying ``Connection: close`` mid-keep-alive gets a
    closing response and EOF; earlier requests on the same connection
    were served keep-alive."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(b"GET /ok HTTP/1.1\r\nHost: x\r\n\r\n")
            first = b""
            while b"hello\n" not in first:
                first += sock.recv(65536)
            sock.sendall(
                b"GET /ok HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
            )
            rest = _recv_all(sock)
            sock.close()
            return first, rest

        first, rest = await loop.run_in_executor(None, client)
        counters = (server.connections_accepted, server.requests_served)
        await server.stop()
        return first, rest, counters

    first, rest, (conns, reqs) = run(scenario(), timeout=30)
    assert b"Connection: keep-alive" in first
    assert b"Connection: close" in rest and rest.endswith(b"hello\n")
    assert conns == 1 and reqs == 2


def test_http10_defaults_to_close(run):
    """HTTP/1.0 without ``Connection: keep-alive`` is one-shot."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(b"GET /ok HTTP/1.0\r\n\r\n")
            data = _recv_all(sock)
            sock.close()
            return data

        data = await loop.run_in_executor(None, client)
        await server.stop()
        return data

    data = run(scenario(), timeout=30)
    assert data.startswith(b"HTTP/1.1 200")
    assert b"Connection: close" in data


def test_idle_keepalive_connection_is_reaped(run):
    """A connection idle past KEEPALIVE_IDLE_TIMEOUT between requests
    is closed by the server (quietly — no 408: the client did nothing
    wrong)."""

    async def scenario():
        server = await _start_server(KEEPALIVE_IDLE_TIMEOUT=0.2)
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(b"GET /ok HTTP/1.1\r\nHost: x\r\n\r\n")
            first = b""
            while b"hello\n" not in first:
                first += sock.recv(65536)
            data = _recv_all(sock)  # EOF, with no error response
            sock.close()
            return data

        data = await loop.run_in_executor(None, client)
        tracked = len(server._conns)  # noqa: SLF001
        await server.stop()
        return data, tracked

    data, tracked = run(scenario(), timeout=30)
    assert data == b""  # reaped: EOF only, no 408 bytes
    assert tracked == 0  # the handler exited and untracked itself


def test_max_requests_cap_retires_the_connection(run):
    async def scenario():
        server = await _start_server(KEEPALIVE_MAX_REQUESTS=2)
        loop = asyncio.get_event_loop()

        def client():
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.bound_port, timeout=5
            )
            headers = []
            for _ in range(2):
                conn.request("GET", "/ok")
                resp = conn.getresponse()
                resp.read()
                headers.append(resp.getheader("Connection"))
            conn.close()
            return headers

        headers = await loop.run_in_executor(None, client)
        await server.stop()
        return headers

    headers = run(scenario(), timeout=30)
    assert headers == ["keep-alive", "close"]


def test_streaming_response_still_closes_the_connection(run):
    """StreamingResponse keeps its close-delimited contract: no
    Content-Length, ``Connection: close``, EOF ends the stream even
    when the request asked for keep-alive."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(
                b"GET /stream HTTP/1.1\r\nHost: x\r\n"
                b"Connection: keep-alive\r\n\r\n"
            )
            data = _recv_all(sock)
            sock.close()
            return data

        data = await loop.run_in_executor(None, client)
        await server.stop()
        return data

    data = run(scenario(), timeout=30)
    assert b"Connection: close" in data
    assert b"Content-Length" not in data
    assert data.endswith(b"data: 1\n\ndata: 2\n\n")


def test_client_disconnect_mid_keepalive_frees_the_handler(run):
    """A client that vanishes between keep-alive requests must not
    leave its handler coroutine parked forever: the read sees EOF and
    the connection untracks itself."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.bound_port, timeout=5
            )
            conn.request("GET", "/ok")
            conn.getresponse().read()
            conn.close()  # abrupt: no Connection: close handshake

        await loop.run_in_executor(None, client)
        for _ in range(100):
            if not server._conns:  # noqa: SLF001
                break
            await asyncio.sleep(0.02)
        tracked = len(server._conns)  # noqa: SLF001
        await server.stop()
        return tracked

    assert run(scenario(), timeout=30) == 0


def test_protocol_error_closes_the_connection(run):
    """After a malformed request the framing is untrusted: 400 is
    answered with ``Connection: close`` and the socket ends, even
    mid-keep-alive."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(b"GET /ok HTTP/1.1\r\nHost: x\r\n\r\n")
            first = b""
            while b"hello\n" not in first:
                first += sock.recv(65536)
            sock.sendall(
                b"GET /ok HTTP/1.1\r\nContent-Length: nope\r\n\r\n"
            )
            data = _recv_all(sock)
            sock.close()
            return data

        data = await loop.run_in_executor(None, client)
        await server.stop()
        return data

    data = run(scenario(), timeout=30)
    assert data.startswith(b"HTTP/1.1 400")
    assert b"Connection: close" in data


def test_pipelined_requests_are_both_answered(run):
    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(
                b"GET /ok HTTP/1.1\r\nHost: x\r\n\r\n"
                b"GET /ok HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
            )
            data = _recv_all(sock)
            sock.close()
            return data

        data = await loop.run_in_executor(None, client)
        counters = (server.connections_accepted, server.requests_served)
        await server.stop()
        return data, counters

    data, (conns, reqs) = run(scenario(), timeout=30)
    assert data.count(b"hello\n") == 2
    assert conns == 1 and reqs == 2


def test_stop_force_closes_idle_keepalive_connections(run):
    """stop() must not leave parked keep-alive handlers behind (nor
    hang on them): lingering idle connections are closed."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        sock = await loop.run_in_executor(
            None,
            lambda: socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            ),
        )

        def request():
            sock.sendall(b"GET /ok HTTP/1.1\r\nHost: x\r\n\r\n")
            data = b""
            while b"hello\n" not in data:
                data += sock.recv(65536)
            return data

        await loop.run_in_executor(None, request)
        await server.stop()  # idle keep-alive connection still open
        data = await loop.run_in_executor(None, lambda: _recv_all(sock))
        sock.close()
        return data

    assert run(scenario(), timeout=30) == b""  # EOF promptly, no hang


def test_oversized_request_line_gets_400_not_task_crash(run):
    """A request line overrunning the StreamReader limit (64KB, no
    newline) raises ValueError inside readline — the client must get
    a 400 + close, never a silent drop via an unhandled task
    exception."""

    async def scenario():
        server = await _start_server()
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(b"GET /" + b"a" * 70000 + b" HTTP/1.1")
            data = _recv_all(sock)
            sock.close()
            return data

        data = await loop.run_in_executor(None, client)
        await server.stop()
        return data

    data = run(scenario(), timeout=30)
    assert data.startswith(b"HTTP/1.1 400")
    assert b"Connection: close" in data


# -- cp-mux/1 conformance (the fleet's multiplexed transport) -----------


async def _mux_upgrade(port):
    """Raw-socket upgrade handshake; returns (reader, writer, head)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        f"GET {MUX_UPGRADE_PATH} HTTP/1.1\r\nHost: x\r\n"
        f"Connection: Upgrade\r\nUpgrade: {MUX_PROTOCOL}\r\n\r\n".encode()
    )
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    return reader, writer, head


def _head_frame(sid, method="GET", path="/ok"):
    return encode_frame(
        FRAME_HEADERS, sid,
        json.dumps({"method": method, "path": path, "headers": {}}).encode(),
    )


async def _mux_connect(port, replica_id="r1"):
    """A MuxConnection (the real fleet client) against a test server."""
    from containerpilot_tpu.fleet.gateway import Replica
    from containerpilot_tpu.fleet.pool import ConnectionPool

    pool = ConnectionPool(mux=True)
    conn = await pool.acquire_mux(
        Replica(replica_id, "127.0.0.1", port), 5.0
    )
    assert conn is not None
    return pool, conn


def test_mux_upgrade_negotiation_and_ping(run):
    """The upgrade earns a 101 and the connection speaks frames:
    PING round-trips as PONG with the payload echoed."""

    async def scenario():
        server = await _start_server()
        reader, writer, head = await _mux_upgrade(server.bound_port)
        writer.write(encode_frame(FRAME_PING, 0, b"nonce-1"))
        await writer.drain()
        pong = await read_frame(reader)
        counters = (server.mux_connections, server.connections_accepted)
        writer.close()
        await server.stop()
        return head, pong, counters

    head, pong, (mux_conns, conns) = run(scenario(), timeout=30)
    assert head.startswith(b"HTTP/1.1 101 ")
    assert b"Upgrade: cp-mux/1" in head
    assert pong == (FRAME_PONG, 0, b"nonce-1")
    assert mux_conns == 1 and conns == 1


def test_mux_adopted_transport_outlives_the_stream_pair(run):
    """The upgrade dials with an asyncio stream pair and then swaps
    the transport's protocol for the frame parser. On Python 3.12
    ``StreamWriter.__del__`` CLOSES its transport, so a connection
    that merely borrowed the transport died with EOF the moment the
    dial's locals were collected (every gateway->replica connection,
    right after the 101). The connection must OWN the pair: alive
    across a collection, closed only by its own close()."""
    import gc

    async def scenario():
        server = await _start_server()
        pool, conn = await _mux_connect(server.bound_port)
        gc.collect()
        await asyncio.sleep(0.05)  # let a close, if any, land as EOF
        alive = not conn.dead and not conn._transport.is_closing()
        writer = getattr(conn, "_writer", None)
        owns = writer is not None and writer.transport is conn._transport
        ponged = await conn.ping(5.0)
        status = 0
        if alive:
            stream = await conn.open_stream("GET", "/ok")
            status, _ = await stream.response_head(5.0)
        pool.close_all()
        closed = conn.dead and conn._transport.is_closing()
        await server.stop()
        return alive, owns, ponged, status, closed

    alive, owns, ponged, status, closed = run(scenario(), timeout=30)
    assert alive, "the adopted transport was closed under the connection"
    assert owns and ponged and status == 200
    assert closed  # and close() closes what it owns


def test_mux_streams_interleave_on_one_connection(run):
    """A fast stream opened AFTER a slow one completes first — the
    whole point of multiplexing: responses interleave per stream, on
    one socket, instead of queueing behind the slowest request."""

    async def scenario():
        server = await _start_server()
        gate = asyncio.Event()

        async def slow(_req):
            await gate.wait()
            return Response(200, b"slow\n")

        server.route("GET", "/slow", slow)
        pool, conn = await _mux_connect(server.bound_port)
        s_slow = await conn.open_stream("GET", "/slow")
        s_fast = await conn.open_stream("GET", "/ok")
        fast_status, _ = await s_fast.response_head(5.0)
        fast_body = await s_fast.read_body(5.0, 1 << 20)
        slow_still_inflight = not s_slow.ended
        gate.set()
        slow_status, _ = await s_slow.response_head(5.0)
        slow_body = await s_slow.read_body(5.0, 1 << 20)
        counters = (
            server.connections_accepted, server.mux_streams_served,
        )
        pool.close_all()
        await server.stop()
        return (
            fast_status, fast_body, slow_still_inflight,
            slow_status, slow_body, counters,
        )

    fast_status, fast_body, inflight, slow_status, slow_body, c = run(
        scenario(), timeout=30
    )
    assert fast_status == 200 and fast_body == b"hello\n"
    assert inflight  # the slow stream had not finished first
    assert slow_status == 200 and slow_body == b"slow\n"
    assert c == (1, 2)  # one socket, two streams


def test_mux_per_stream_backpressure(run):
    """A stream whose consumer stops granting WINDOW credit stalls
    ALONE at its window: the co-resident stream still completes, and
    draining the stalled stream releases the rest."""

    async def scenario():
        server = await _start_server()
        big = b"x" * (200 * 1024)  # > MUX_INITIAL_WINDOW (64KB)

        async def bulk(_req):
            async def gen():
                yield big

            return StreamingResponse(gen(), content_type="text/plain")

        server.route("GET", "/bulk", bulk)
        pool, conn = await _mux_connect(server.bound_port)
        s_bulk = await conn.open_stream("GET", "/bulk")
        await s_bulk.response_head(5.0)
        first = await s_bulk.read_chunk(5.0)  # grants a little credit
        # stop consuming /bulk: the server's writer for that stream
        # must park on its window while /ok flows freely
        s_ok = await conn.open_stream("GET", "/ok")
        ok_status, _ = await s_ok.response_head(5.0)
        ok_body = await s_ok.read_body(5.0, 1 << 20)
        # now drain the parked stream to completion
        rest = first
        while True:
            chunk = await s_bulk.read_chunk(5.0)
            if not chunk:
                break
            rest += chunk
        pool.close_all()
        await server.stop()
        return ok_status, ok_body, rest

    ok_status, ok_body, rest = run(scenario(), timeout=30)
    assert ok_status == 200 and ok_body == b"hello\n"
    assert rest == b"x" * (200 * 1024)  # nothing lost to the stall


def test_mux_cancel_mid_stream_runs_handler_cleanup(run):
    """CANCEL mid-DATA: the streaming handler's close callback and
    generator-finally both run, the stream id is freed, and the
    CONNECTION keeps serving other streams."""

    async def scenario():
        server = await _start_server()
        cleaned = {"finally": False, "close": False}

        async def endless(_req):
            async def gen():
                try:
                    while True:
                        yield b"tick\n"
                        await asyncio.sleep(0.01)
                finally:
                    cleaned["finally"] = True

            return StreamingResponse(
                gen(), close=lambda: cleaned.__setitem__("close", True)
            )

        server.route("GET", "/endless", endless)
        pool, conn = await _mux_connect(server.bound_port)
        stream = await conn.open_stream("GET", "/endless")
        await stream.response_head(5.0)
        assert await stream.read_chunk(5.0)  # mid-DATA
        assert stream.cancel()
        for _ in range(100):
            if cleaned["finally"] and cleaned["close"]:
                break
            await asyncio.sleep(0.02)
        # the shared connection survived the cancel
        s_ok = await conn.open_stream("GET", "/ok")
        ok_status, _ = await s_ok.response_head(5.0)
        await s_ok.read_body(5.0, 1 << 20)
        alive = await conn.ping()
        counters = server.connections_accepted
        pool.close_all()
        await server.stop()
        return dict(cleaned), ok_status, alive, counters

    cleaned, ok_status, alive, conns = run(scenario(), timeout=30)
    assert cleaned == {"finally": True, "close": True}
    assert ok_status == 200 and alive
    assert conns == 1


def test_mux_protocol_error_closes_the_connection(run):
    """Garbage framing (unknown frame type) kills the whole
    connection — its framing can no longer be trusted, exactly like a
    400 on the HTTP/1.1 path — and in-flight streams see EOF."""

    async def scenario():
        server = await _start_server()
        reader, writer, _ = await _mux_upgrade(server.bound_port)
        writer.write(_head_frame(1) + encode_frame(FRAME_END, 1))
        resp_head = await read_frame(reader)
        writer.write(b"\x00\x00\x00\x04\xff\x00\x00\x00\x01zzzz")
        await writer.drain()
        leftover = await reader.read()  # EOF after any buffered frames
        writer.close()
        await server.stop()
        return resp_head[0], leftover

    ftype, leftover = run(scenario(), timeout=30)
    assert ftype == FRAME_HEADERS
    # whatever was in flight, the server closed the connection: the
    # read drained to EOF instead of hanging on more frames
    assert leftover is not None


def test_mux_abort_rsts_all_streams(run):
    """abort() (SIGKILL semantics) fails every in-flight stream
    promptly and exactly once — each failure arms the caller's retry,
    none hangs."""
    from containerpilot_tpu.fleet.pool import UpstreamError

    async def scenario():
        server = await _start_server()
        gate = asyncio.Event()

        async def stuck(_req):
            await gate.wait()
            return Response(200, b"never\n")

        server.route("GET", "/stuck", stuck)
        pool, conn = await _mux_connect(server.bound_port)
        s1 = await conn.open_stream("GET", "/stuck")
        s2 = await conn.open_stream("GET", "/stuck")
        await asyncio.sleep(0.05)
        await server.abort()
        errors = []
        for stream in (s1, s2):
            try:
                await stream.response_head(5.0)
            except UpstreamError as exc:
                errors.append(exc)
        dead = conn.dead
        pool.close_all()
        return len(errors), dead

    n_errors, dead = run(scenario(), timeout=30)
    assert n_errors == 2 and dead


def test_mux_negotiation_fallback_to_http11(run):
    """A server with mux disabled answers the upgrade through the
    route table (404, keep-alive): acquire_mux reports 'no mux' AND
    pools the probe socket, so the classic path rides the very same
    connection — zero wasted dials."""
    from containerpilot_tpu.fleet.gateway import Replica
    from containerpilot_tpu.fleet.pool import ConnectionPool

    async def scenario():
        server = await _start_server(mux_enabled=False)
        pool = ConnectionPool(mux=True)
        replica = Replica("r1", "127.0.0.1", server.bound_port)
        conn = await pool.acquire_mux(replica, 5.0)
        idle = pool.idle_count("r1")
        stats = pool.mux_stats("r1")
        # the classic path reuses the probe's socket
        pooled = await pool.acquire(replica, 5.0)
        counters = server.connections_accepted
        pool.release(pooled)
        pool.close_all()
        await server.stop()
        return conn, idle, stats, counters

    conn, idle, stats, conns = run(scenario(), timeout=30)
    assert conn is None
    assert idle == 1 and stats["unsupported"] is True
    assert conns == 1  # probe socket reused, not burned


def test_plain_http_clients_unchanged_on_mux_server(run):
    """A client that never sends the upgrade gets byte-identical
    HTTP/1.1 from a mux-enabled server: keep-alive headers, framing,
    and counters exactly as the keep-alive suite pins them."""

    async def scenario():
        server = await _start_server()  # mux_enabled defaults True
        loop = asyncio.get_event_loop()

        def client():
            sock = socket.create_connection(
                ("127.0.0.1", server.bound_port), timeout=5
            )
            sock.sendall(b"GET /ok HTTP/1.1\r\nHost: x\r\n\r\n")
            first = b""
            while b"hello\n" not in first:
                first += sock.recv(65536)
            sock.close()
            return first

        data = await loop.run_in_executor(None, client)
        counters = (server.mux_connections, server.mux_streams_served)
        await server.stop()
        return data, counters

    data, (mux_conns, mux_streams) = run(scenario(), timeout=30)
    assert data.startswith(b"HTTP/1.1 200 OK\r\n")
    assert b"Connection: keep-alive" in data
    assert b"cp-mux" not in data  # no mux artifacts leak
    assert mux_conns == 0 and mux_streams == 0


def test_mux_stream_cap_refuses_excess_stream_with_503(run):
    """The stream cap refuses the EXCESS stream with a per-stream
    503 — retryable by the gateway — while the connection and its
    live streams are untouched."""

    async def scenario():
        server = await _start_server(MUX_MAX_STREAMS=1)
        gate = asyncio.Event()

        async def stuck(_req):
            await gate.wait()
            return Response(200, b"first\n")

        server.route("GET", "/stuck", stuck)
        pool, conn = await _mux_connect(server.bound_port)
        s1 = await conn.open_stream("GET", "/stuck")
        s2 = await conn.open_stream("GET", "/ok")
        refused_status, refused_headers = await s2.response_head(5.0)
        await s2.read_body(5.0, 1 << 20)
        gate.set()
        ok_status, _ = await s1.response_head(5.0)
        body = await s1.read_body(5.0, 1 << 20)
        pool.close_all()
        await server.stop()
        return refused_status, refused_headers, ok_status, body

    refused, headers, ok_status, body = run(scenario(), timeout=30)
    assert refused == 503 and headers.get("retry-after")
    assert ok_status == 200 and body == b"first\n"
