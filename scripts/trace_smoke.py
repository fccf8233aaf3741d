#!/usr/bin/env python
"""trace-smoke: prove the cross-hop stitched timeline on a live fleet.

Boots the ``make fleet-smoke`` topology for real — two in-process
``InferenceServer`` replicas (slot engine on, so SSE works), a
``FleetMember`` each heartbeating a file catalog, one ``FleetGateway``
over the cp-mux/1 transport (the default) — then issues ONE buffered
and ONE SSE ``/v1/generate`` through the gateway and asserts, for
each, from ``GET /v1/traces``:

- **stitched, >= 2 hops**: the gateway's timeline for that trace id
  carries both gateway-side spans (admission_queue_wait,
  upstream_connect/ttfb) and spliced ``replica.*`` spans, and the
  SAME trace id appears in one replica's own /v1/traces ring — two
  processes' views of one request, joined by the id the gateway
  minted;
- **non-overlapping stage accounting within tolerance**: the
  top-level gateway stages partition the request — their summed
  duration never exceeds the trace's wall time by more than the
  tolerance — and every replica child span lands inside the trace
  window (clock skew across hops is bounded by the in-process
  network, so the alignment at the dispatch span must hold);
- **over mux**: the replica that served it shows opened mux streams
  on the gateway's /fleet snapshot (the hop really rode cp-mux/1).

Exit 0 on success, 1 with the offending evidence on stderr.
Wired as ``make trace-smoke`` next to ``fleet-smoke``.
"""
import asyncio
import json
import os
import sys
import tempfile
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from containerpilot_tpu.discovery import FileCatalogBackend  # noqa: E402
from containerpilot_tpu.fleet import FleetGateway, FleetMember  # noqa: E402
from containerpilot_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_params,
)
from containerpilot_tpu.workload.serve import InferenceServer  # noqa: E402

#: slack for summed-stage accounting and replica-span alignment (ms):
#: covers timer granularity + the header-write gap between span ends
#: and trace finish on a loaded 1-core box
TOLERANCE_MS = 25.0
SERVICE = "inference"


def _get(port: int, path: str):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as resp:
        return resp.status, resp.read(), dict(resp.headers)


def _post(port: int, payload: dict):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, resp.read(), dict(resp.headers)


def _post_sse(port: int, payload: dict):
    """Read a whole SSE response; returns (trace_id_header, events)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        headers = dict(resp.headers)
        raw = resp.read()
    events = []
    for blob in raw.split(b"\n\n"):
        if blob.startswith(b"data: "):
            events.append(json.loads(blob[len(b"data: "):]))
    return headers, events


def _fail(msg: str, evidence=None) -> None:
    print(f"trace-smoke: FAIL: {msg}", file=sys.stderr)
    if evidence is not None:
        print(json.dumps(evidence, indent=2)[:4000], file=sys.stderr)
    raise SystemExit(1)


def _find_trace(snapshot: dict, trace_id: str) -> dict:
    for entry in snapshot["recent"] + snapshot["slowest"]:
        if entry["trace_id"] == trace_id:
            return entry
    _fail(f"trace {trace_id} not in /v1/traces", snapshot)


def _check_stitched(entry: dict, want_stages) -> None:
    stages = {s["stage"] for s in entry["spans"]}
    missing = set(want_stages) - stages
    if missing:
        _fail(f"{entry['trace_id']}: missing stages {missing}", entry)
    if not any(s.startswith("replica.") for s in stages):
        _fail(
            f"{entry['trace_id']}: no replica.* spans — the timeline "
            f"is single-hop, not stitched", entry,
        )


def _check_accounting(entry: dict) -> None:
    duration = entry["duration_ms"]
    top_sum = sum(
        s["dur_ms"]
        for s in entry["spans"]
        if not s["stage"].startswith("replica.")
    )
    if top_sum > duration + TOLERANCE_MS:
        _fail(
            f"{entry['trace_id']}: top-level stages sum to "
            f"{top_sum:.2f}ms > duration {duration:.2f}ms + "
            f"{TOLERANCE_MS}ms — stages overlap", entry,
        )
    for s in entry["spans"]:
        if not s["stage"].startswith("replica."):
            continue
        if s["offset_ms"] < -TOLERANCE_MS or (
            s["offset_ms"] + s["dur_ms"] > duration + TOLERANCE_MS
        ):
            _fail(
                f"{entry['trace_id']}: replica span {s['stage']} "
                f"falls outside the trace window", entry,
            )


async def main() -> int:
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    servers, members = [], []
    with tempfile.TemporaryDirectory(prefix="trace-smoke-") as root:
        backend = FileCatalogBackend(root)
        for i in range(2):
            server = InferenceServer(
                cfg, params, "127.0.0.1", 0, max_len=64,
                slots=2, slot_chunk=4,
            )
            await server.run()
            member = FleetMember(
                server, backend, SERVICE, ttl=30,
                heartbeat_interval=0.2, instance_id=f"replica-{i}",
            )
            await member.start()
            servers.append(server)
            members.append(member)
        gateway = FleetGateway(
            backend, SERVICE, "127.0.0.1", 0,
            poll_interval=0.2, hedge=False,
        )
        await gateway.run()
        for _ in range(200):
            if gateway.replica_count == 2:
                break
            await asyncio.sleep(0.05)
        if gateway.replica_count != 2:
            _fail(f"fleet never converged: {gateway.replica_count}/2")

        loop = asyncio.get_event_loop()
        # one buffered, one SSE — both ride cp-mux/1 (the default)
        status, _body, headers = await loop.run_in_executor(
            None, _post, gateway.port,
            {"tokens": [[1, 2, 3]], "max_new_tokens": 6, "seed": 1},
        )
        if status != 200:
            _fail(f"buffered request answered {status}")
        buffered_id = headers.get("X-CP-Trace", "")
        if not buffered_id:
            _fail("buffered answer carried no X-CP-Trace", headers)
        if not headers.get("X-CP-Span-Digest"):
            _fail("buffered answer carried no span digest", headers)
        sse_headers, events = await loop.run_in_executor(
            None, _post_sse, gateway.port,
            {
                "tokens": [[4, 5, 6]], "max_new_tokens": 6,
                "seed": 2, "stream": True,
            },
        )
        if not events or events[-1].get("done") is not True:
            _fail("SSE stream ended without its done event", events)
        sse_id = sse_headers.get("X-CP-Trace", "")
        if not sse_id:
            _fail("SSE answer carried no X-CP-Trace", sse_headers)
        if not isinstance(events[-1].get("spans"), str):
            _fail(
                "SSE done frame carried no replica span digest",
                events[-1],
            )

        _status, body, _ = await loop.run_in_executor(
            None, _get, gateway.port, "/v1/traces"
        )
        snapshot = json.loads(body)
        buffered = _find_trace(snapshot, buffered_id)
        streamed = _find_trace(snapshot, sse_id)
        _check_stitched(
            buffered,
            ("admission_queue_wait", "upstream_connect",
             "upstream_ttfb", "replica.prefill", "replica.decode"),
        )
        _check_stitched(
            streamed,
            ("admission_queue_wait", "upstream_ttfb", "relay",
             "replica.prefill", "replica.stream_relay"),
        )
        _check_accounting(buffered)
        _check_accounting(streamed)

        # cross-hop for real: the SAME ids live in a replica's ring
        for trace_id in (buffered_id, sse_id):
            found = False
            for server in servers:
                _s, body, _h = await loop.run_in_executor(
                    None, _get, server.port, "/v1/traces"
                )
                replica_snap = json.loads(body)
                if any(
                    e["trace_id"] == trace_id
                    for e in replica_snap["recent"]
                ):
                    found = True
                    break
            if not found:
                _fail(
                    f"trace {trace_id} not found in any replica's "
                    f"/v1/traces — the id did not propagate"
                )

        # and it rode mux: the gateway opened streams to its replicas
        _s, body, _h = await loop.run_in_executor(
            None, _get, gateway.port, "/fleet"
        )
        fleet = json.loads(body)
        opened = sum(
            r["mux"]["streams_opened"] for r in fleet["replicas"]
        )
        if opened < 2:
            _fail(
                f"only {opened} mux streams opened — the hops did "
                f"not ride cp-mux/1", fleet,
            )
        if fleet.get("catalog_poll_age_s") is None:
            _fail("/fleet reports no catalog_poll_age_s", fleet)

        await gateway.stop()
        for member in members:
            await member.stop()
        for server in servers:
            await server.stop()

    print(
        "trace-smoke: OK — buffered "
        f"{buffered_id} ({buffered['duration_ms']}ms, dominant "
        f"{buffered.get('dominant_stage')}) and SSE {sse_id} "
        f"({streamed['duration_ms']}ms, dominant "
        f"{streamed.get('dominant_stage')}) stitched across "
        "gateway + replica over cp-mux/1"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
