"""The load driver: an asyncio client for the gateway's SSE streams.

Copied from ``containerpilot_tpu/chaos/client.py`` with its zero moved:
time to first token runs from when the request was DUE on the
schedule, not from when it was sent, and how late the generator ran is
recorded per request. One connection per request (each models an
independent end client). Nothing is retried: a refused or failed
request counts as failed.
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .loadgen import Request

REQUEST_TIMEOUT_S = 120.0


def parse_digest(digest: str) -> Dict[str, float]:
    """``stage~offset_ms~dur_ms;...`` -> summed seconds per stage
    (the wire format of telemetry/tracing.py's span digest)."""
    totals: Dict[str, float] = {}
    for part in (digest or "").split(";"):
        fields = part.split("~")
        if len(fields) != 3 or not fields[0]:
            continue
        try:
            dur = max(float(fields[2]), 0.0) / 1e3
        except ValueError:
            continue
        totals[fields[0]] = totals.get(fields[0], 0.0) + dur
    return totals


def new_record(req: Request, due_s: float) -> Dict[str, Any]:
    return {
        "index": req.index, "session_id": req.session_id,
        "prompt_len": len(req.tokens), "max_new": req.max_new_tokens,
        "shared_tokens": req.shared_tokens,
        "due_s": due_s, "sent_s": None, "status": 0, "error": "",
        "first_s": None, "last_s": None, "arrivals": [], "tokens": [],
        "done": False, "cut": False, "stages": {}, "replica_stages": {},
        "prompt": req.tokens,
    }


async def _read_head(reader: asyncio.StreamReader) -> Tuple[int, Dict[str, str]]:
    blob = await reader.readuntil(b"\r\n\r\n")
    lines = blob.split(b"\r\n")
    status = int(lines[0].decode("latin-1").split(None, 2)[1])
    headers: Dict[str, str] = {}
    for line in lines[1:]:
        key, _, value = line.decode("latin-1").partition(":")
        if key:
            headers[key.strip().lower()] = value.strip()
    return status, headers


async def issue(
    port: int, req: Request, clock_zero: float, due_s: float,
    cut_at: Optional[float] = None,
) -> Dict[str, Any]:
    """Send one request and read its stream to the ``done`` frame.
    Times are seconds since ``clock_zero`` (time.monotonic()). With
    ``cut_at`` the client hangs up at that instant (the window
    closed); such a request is marked ``cut``, not failed. Never
    raises: failures land in ``error``."""
    record = new_record(req, due_s)
    writer: Optional[asyncio.StreamWriter] = None

    def left() -> float:
        if cut_at is None:
            return REQUEST_TIMEOUT_S
        return max(cut_at - (time.monotonic() - clock_zero), 0.0)

    try:
        body = json.dumps(req.payload()).encode()
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection("127.0.0.1", port), REQUEST_TIMEOUT_S
        )
        head = (
            f"POST /v1/generate HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
        )
        record["sent_s"] = time.monotonic() - clock_zero
        writer.write(head.encode() + body)
        await writer.drain()
        status, headers = await asyncio.wait_for(_read_head(reader), left() or 1e-3)
        record["status"] = status
        record["stages"] = parse_digest(headers.get("x-cp-span-digest", ""))
        if status != 200 or "text/event-stream" not in headers.get(
            "content-type", ""
        ):
            record["error"] = f"status {status}"
            return record
        buffer = b""
        while True:
            chunk = await asyncio.wait_for(reader.read(65536), left() or 1e-3)
            now = time.monotonic() - clock_zero
            if not chunk:
                record["error"] = "stream ended without its done frame"
                return record
            buffer += chunk
            while b"\n\n" in buffer:
                raw, buffer = buffer.split(b"\n\n", 1)
                if not raw.startswith(b"data: "):
                    continue
                event = json.loads(raw[len(b"data: "):])
                if event.get("done"):
                    record["done"] = True
                    record["replica_stages"] = parse_digest(
                        event.get("spans") or ""
                    )
                    if event.get("count") != len(record["tokens"]):
                        record["error"] = (
                            f"done.count {event.get('count')} != "
                            f"{len(record['tokens'])} streamed tokens"
                        )
                    return record
                delta = [int(t) for t in event.get("tokens") or []]
                if delta:
                    if record["first_s"] is None:
                        record["first_s"] = now
                    record["last_s"] = now
                    record["arrivals"].append([now, len(delta)])
                    record["tokens"].extend(delta)
    except asyncio.TimeoutError:
        if cut_at is not None and time.monotonic() - clock_zero >= cut_at - 1e-3:
            record["cut"] = True
        else:
            record["error"] = "timed out"
    except (OSError, asyncio.IncompleteReadError, ValueError) as exc:
        record["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        if writer is not None:
            writer.close()
    return record


async def run_closed(
    port: int, requests: Iterator[Request], clients: int, window_s: float
) -> Tuple[List[Dict[str, Any]], float]:
    """``clients`` loops, each sending its next request when the last
    ended, for ``window_s``; requests in flight at the close are cut.
    Returns (records, the clock's zero)."""
    clock_zero = time.monotonic()
    records: List[Dict[str, Any]] = []

    async def loop() -> None:
        while time.monotonic() - clock_zero < window_s:
            req = next(requests)
            due = time.monotonic() - clock_zero
            records.append(await issue(port, req, clock_zero, due, window_s))

    await asyncio.gather(*(loop() for _ in range(clients)))
    return records, clock_zero


async def run_open(
    port: int, schedule: List[Request], window_s: float, drain_s: float
) -> Tuple[List[Dict[str, Any]], float]:
    """Send each request when it is due, whatever the earlier ones are
    doing. After the window every request due inside it may finish
    for ``drain_s`` more; what is unfinished then is cut."""
    clock_zero = time.monotonic()

    async def one(req: Request) -> Dict[str, Any]:
        delay = req.due_s - (time.monotonic() - clock_zero)
        if delay > 0:
            await asyncio.sleep(delay)
        return await issue(port, req, clock_zero, req.due_s,
                           window_s + drain_s)

    records = await asyncio.gather(*(one(r) for r in schedule))
    return list(records), clock_zero


async def run_sequence(port: int, requests: List[Request]) -> List[Dict[str, Any]]:
    """Warm-up: one request after another."""
    clock_zero = time.monotonic()
    out = []
    for req in requests:
        out.append(await issue(port, req, clock_zero,
                               time.monotonic() - clock_zero))
    return out
