"""Shared keep-alive discipline for synchronous http.client callers.

Two clients keep a connection across calls — the ControlClient (unix
socket, one conn per client) and the ConsulBackend (TCP, one conn per
thread). Both need the same subtle state machine, so it lives here
once:

- take the kept connection, else dial a fresh one;
- a KEPT connection that fails **before any response byte arrived**
  gets one transparent redial-and-resend: a reset/broken-pipe while
  SENDING means the server never took the full request, and
  ``RemoteDisconnected`` from ``getresponse()`` means the server
  closed without answering a byte — overwhelmingly the idle reaper
  racing our send. This is the standard keep-alive client heuristic
  (urllib3, Go's http.Transport do the same), not a guarantee: a
  server that processed the request and then died before writing ANY
  response byte is indistinguishable from a reap, so a verb can
  double-apply in that narrow crash window. Callers whose verbs
  can't tolerate that must not share a kept connection;
- a failure AFTER ``getresponse()`` returned (a reset mid-body, a
  garbled status line) is NOT resent — response bytes prove the
  server received and likely processed the request;
- the connection is kept again only when the response wasn't
  ``Connection: close``.

Transport exceptions propagate unchanged; callers wrap them in their
own error types (and own any connect-phase retry policy).
"""
from __future__ import annotations

import http.client
from typing import Callable, Dict, Optional, Tuple

_tracing = None


def _trace_id() -> str:
    """The active trace id, lazily bound: a control-plane or catalog
    call made while serving a traced request carries the request's
    X-CP-Trace, so cross-service log/trace greps pick it up too."""
    global _tracing
    if _tracing is None:
        from ..telemetry import tracing as _tracing_mod

        _tracing = _tracing_mod
    return _tracing.current_trace_id()


def keepalive_request(
    take_conn: Callable[[], Optional[http.client.HTTPConnection]],
    put_conn: Callable[[http.client.HTTPConnection], None],
    new_conn: Callable[[], http.client.HTTPConnection],
    method: str,
    path: str,
    body=None,
    headers: Optional[Dict[str, str]] = None,
) -> Tuple[int, bytes]:
    """One request over the kept connection; returns (status, body).

    Raises whatever the transport raised (OSError /
    http.client.HTTPException) once the kept-connection redial is
    exhausted — at most one redial happens, since the redialed
    connection is fresh. See the module docstring for the resend
    heuristic's (narrow) double-apply window."""
    send_headers = dict(headers or {})
    trace_id = _trace_id()
    if trace_id and "X-CP-Trace" not in send_headers:
        send_headers["X-CP-Trace"] = trace_id
    while True:
        conn = take_conn()
        reused = conn is not None
        if conn is None:
            conn = new_conn()
        try:
            conn.request(method, path, body=body, headers=send_headers)
        except (OSError, http.client.HTTPException) as exc:
            conn.close()
            if reused and isinstance(exc, ConnectionError):
                continue  # send bounced off the reaped kept conn
            raise
        try:
            resp = conn.getresponse()
            payload = resp.read()
        except (OSError, http.client.HTTPException) as exc:
            conn.close()
            if reused and isinstance(exc, http.client.RemoteDisconnected):
                # closed without a single response byte: not processed
                continue
            raise
        if resp.will_close:
            conn.close()
        else:
            put_conn(conn)
        return resp.status, payload
