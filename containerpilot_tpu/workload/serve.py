"""A supervised inference server: the serving half of the demo workload.

One server process per TPU host, supervised by containerpilot-tpu:
health-checked over ``GET /health`` (so a wedged server goes
catalog-critical and restarts), advertised in the catalog by its job's
``port``, optionally loading weights from a training checkpoint dir.

API (token-level; tokenization is the caller's concern):

    POST /v1/generate {"tokens": [[1,2,3]], "max_new_tokens": 16,
                       "temperature": 0.0}
        -> {"tokens": [[...generated ids...]]}
        ("logprobs": true echoes per-token logprobs of the trimmed
         output via one teacher-forced pass — decode is bit-equal to
         the forward, so these are exactly the sampler's numbers;
         approximate only under --kv-int8, whose decode reads a
         quantized KV cache)
    POST /v1/score    {"tokens": [[1,2,3,4]]}
        -> {"logprobs": [[lp(t1|t0), lp(t2|t0..1), ...]],
            "sums": [total lp per row]}   (teacher-forced scoring)
    POST /v1/completions {"prompt": "text", ...}   (behind --text)
        -> {"text": "...", "tokens": [...]}  (byte-level tokenizer)
    GET /health   -> 200 once the model is compiled and warm
    GET /v1/model -> config summary
    GET /metrics  -> Prometheus exposition (requests, latency, tokens)

Generation runs on worker threads so the asyncio loop (health checks
included) never blocks on TPU execution. Every sampled generate
request rides the slot engine, one row at a time (serve_slots +
models/stepprog: the step-program engine, plain, quantized and
speculative decode; long prompts, context-parallel prefill and prefix
KV reuse are the engine's admission policy, serve_prefix holding the
cache). Beam search alone is a one-shot call (models/beam.py).
serve_cli has the flags and the model loading.
"""
from __future__ import annotations

import asyncio
import json
import logging
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Awaitable, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..models.quantized import resident_weights
from ..models.transformer import TransformerConfig
from ..telemetry import tracing
from ..utils.http import HTTPServer, Request, Response, StreamingResponse
from .serve_cli import main  # noqa: F401  (one import path for the CLI)
from .serve_prefix import PrefixCache
from .serve_slots import SlotEngine

log = logging.getLogger("containerpilot.serve")

# warmup()'s slot-engine dummy request: this many prompt ids +
# (chunk+1) new tokens. The construction-time max_len guard and the
# warm request itself must agree or the guard stops protecting.
WARMUP_PROMPT_LEN = 4


def _parse_token_rows(body: Dict[str, Any], vocab: int, min_row_len: int):
    """Shared request validation for token-matrix endpoints: a
    non-empty list of equal-length integer rows within the vocab.
    Raises ValueError with a client-facing message."""
    tokens = body["tokens"]
    if not isinstance(tokens, list) or not tokens or not all(
        isinstance(row, list) and len(row) >= min_row_len for row in tokens
    ):
        raise ValueError(
            f"'tokens' must be a non-empty list of rows with "
            f">= {min_row_len} ids"
        )
    row_len = len(tokens[0])
    if any(len(row) != row_len for row in tokens):
        raise ValueError("all rows must share a length (pad first)")
    if any(
        not isinstance(t, int) or isinstance(t, bool) or t < 0 or t >= vocab
        for row in tokens
        for t in row
    ):
        raise ValueError(f"token ids must be integers in [0, {vocab})")
    return tokens, row_len


class InferenceServer:
    def __init__(
        self,
        cfg: TransformerConfig,
        params: Any,
        host: str,
        port: int,
        max_len: int,
        draft_layers: int = 0,
        speculate: int = 4,
        max_batch_rows: int = 16,
        prefix_cache_entries: int = 0,
        kv_spill_bytes: int = 0,
        prefill_chunk: int = 0,
        text: bool = False,
        slots: int = 4,
        slot_chunk: int = 8,
        slot_window: int = 4,
        cp_mesh: Any = None,
        cp_min_len: int = 0,
        mux: bool = True,
        role: str = "active",
        compile_cache_dir: str = "",
        prefill_floor_s: float = 0.0,
        started_at: Optional[float] = None,
    ) -> None:
        self.cfg = cfg
        self.params = params
        self.host = host
        self.port = port
        self.max_len = max_len
        self.ready = False
        # fleet role: a "standby" replica boots, loads weights, and
        # warmup-compiles exactly like an active one, but /health says
        # so (503 standby) and new decode work is refused — it
        # heartbeats into the catalog under role=standby and waits for
        # POST /v3/standby/promote to flip it active in one
        # assignment (fleet/standby.py is the pool that promotes).
        # "prefill" and "decode" are the disaggregated pools' phase
        # roles: both serve traffic and answer /health 200 like an
        # active replica (so degradation to mixed routing always has
        # somewhere to go) — the role is ROUTING ADVICE the gateway
        # reads off the same heartbeat note channel, steering fresh
        # prompts at the prefill pool and decode continuations at the
        # decode pool (fleet/gateway.py's phase-aware _pick).
        if role not in ("active", "standby", "prefill", "decode"):
            raise ValueError(
                "role must be 'active', 'standby', 'prefill', or "
                "'decode'"
            )
        self.role = role
        # the directory this process's persistent XLA compile cache is
        # in force at (modelcfg.enable_compile_cache's return — the
        # server itself NEVER sets a cache directory): warmup consults
        # the warm-bucket marker there and skips buckets a previous
        # process already compiled, and heartbeats advertise it
        # (cc=). Empty = no marker, no advertisement.
        self.compile_cache_dir = compile_cache_dir
        # the cc= heartbeat advertisement, computed once at warmup
        # end (executor-wrapped): heartbeats must never pay marker
        # file I/O on the serving loop
        self._compile_cache_note = ""
        # peer weight transfer: the manifest is built once (executor)
        # and cached — chunk bytes are re-derived lazily per request
        # so the server never holds a second full copy of the params
        self._weights_manifest_cache: Optional[Any] = None
        self._weights_manifest_bytes = b""
        self._weights_lock: Optional[asyncio.Lock] = None
        # device-time ledger (telemetry/goodput.py): every wall-second
        # of this replica's life attributed to exactly one stage,
        # starting in ``boot`` at ``started_at`` (a time.monotonic
        # stamp; the CLI passes the PROCESS's start, so interpreter
        # start, the jax import and weight init are costed too) or,
        # without one, now — engine construction and port binding are
        # costed before warmup() moves the ledger to compile_warmup
        # and, finally, idle (before /health flips 200, so a scale-up
        # replica's badput is visible from its very first scrape)
        from ..telemetry.goodput import DeviceTimeLedger

        self.ledger = DeviceTimeLedger(now=started_at)
        # maintenance drain: /health goes 503 and NEW generate/
        # completions are rejected with 503 + Retry-After while
        # everything already admitted (including running slot-engine
        # rows) decodes to completion. Flipped by enter_maintenance/
        # exit_maintenance — the hook fleet.FleetMember drives off the
        # control plane's /v3/maintenance endpoints.
        self.draining = False
        self._inflight = 0
        # drain migration (kvtier/handoff.py in reverse): progress of
        # the CURRENT evacuation plus cumulative counters for the
        # ``mg=`` heartbeat field. ``landed`` maps fingerprint ->
        # target instance id, most-recent-last (the note encoder
        # reverses it so truncation drops the oldest repoints); the
        # gateway repoints its sticky pins off these landings.
        self.migration: Dict[str, Any] = {
            "active": False, "total": 0, "done": 0, "failed": 0,
            "timeout": 0, "window_s": 0.0, "started_at": 0.0,
        }
        self._migration_landed: "OrderedDict[int, str]" = OrderedDict()
        self._migration_counters = {
            "done": 0, "total": 0, "failed": 0, "timeout": 0,
        }
        # test-only fault-injection seam (chaos harness): when set,
        # awaited before every instrumented API handler. Injects
        # per-request latency (slow-replica brownouts) or raises to
        # fail requests, without touching any serving path. Never set
        # in production; None costs one attribute load per request.
        self.chaos_hook: Optional[
            Callable[[str], Awaitable[None]]
        ] = None
        # context-parallel prefill: prompts at least cp_min_len long
        # ring their prefill over the mesh's seq axis on admission to
        # the slot engine. Composition is validated at startup below.
        self.cp_mesh = cp_mesh
        self.cp_min_len = cp_min_len
        if cp_mesh is not None:
            seq_axis = cp_mesh.shape.get("seq", 1)
            if seq_axis <= 1:
                raise ValueError(
                    "--cp mesh needs a seq axis > 1 "
                    "(MeshPlan(seq=...))"
                )
            # ONE policy for deriving/clamping/refusing the threshold,
            # shared with the pod's --sp (parallel/context.py)
            from ..parallel.context import resolve_cp_min_len

            self.cp_min_len = resolve_cp_min_len(
                cp_min_len, seq_axis, max_len
            )
            for flag, why in (
                (draft_layers > 0, "--draft-layers (speculative "
                 "prefill is chunk-driven)"),
                (prefix_cache_entries > 0, "--prefix-cache (cached "
                 "prefixes bypass the ring)"),
                (cfg.window > 0, "--window (ring attention rejects "
                 "sliding windows)"),
            ):
                if flag:
                    raise ValueError(
                        f"--cp does not compose with {why}"
                    )
        # self-speculative decoding: a layer-prefix draft accelerates
        # greedy single-sequence generation, output unchanged
        self.draft_params = self.draft_cfg = None
        self.speculate = speculate
        if draft_layers > 0 and speculate < 1:
            # fail at startup, not as request-time 500s
            raise ValueError("speculate must be >= 1")
        if draft_layers > 0 and cfg.window > 0:
            raise ValueError(
                "--draft-layers does not compose with --window "
                "(speculative rollback cannot undo ring-cache writes)"
            )
        if prefix_cache_entries > 0 and cfg.window > 0:
            raise ValueError(
                "--prefix-cache does not compose with --window (a "
                "ring cache's stale rows are live window context, so "
                "a shorter-prefix rewind cannot reuse them)"
            )
        if getattr(cfg, "recurrent_state", False):
            for flag, on in (("--prefix-cache", prefix_cache_entries > 0),
                             ("--kv-spill-mb", kv_spill_bytes > 0)):
                if on:
                    raise ValueError(
                        f"{flag} does not compose with this model: a "
                        "recurrent state cannot be rewound to a shorter "
                        "prefix, and no row of it is stored, spilled "
                        "or handed off yet"
                    )
        if prefill_chunk > 0 and getattr(cfg, "one_token_steps", False):
            raise ValueError(
                "--prefill-chunk does not compose with this model: its "
                "decode step takes one token a row (a window layer's "
                "ring is written before it is read), so a prompt is "
                "not extended in pieces yet"
            )
        if kv_spill_bytes > 0 and prefix_cache_entries <= 0:
            raise ValueError(
                "--kv-spill requires --prefix-cache (the spill tier "
                "catches the prefix cache's evictions)"
            )
        spill = None
        if kv_spill_bytes > 0:
            # host-RAM floor under the device LRU: evictions spill,
            # later matches readmit via device_put (kvtier/spill.py)
            from ..kvtier import HostSpillTier

            spill = HostSpillTier(kv_spill_bytes)
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(prefix_cache_entries, spill=spill)
            if prefix_cache_entries > 0 else None
        )
        # continuous decode admission: every sampled sequence joins a
        # running K-token chunk loop over a fixed slot pool
        # (serve_slots.py). ``slots`` is a capacity (KV memory scales
        # with it), never a switch: there is no server without a pool.
        if slots < 1:
            raise ValueError(
                "slots must be >= 1 (every generate request rides the "
                "slot engine; the pool's size is its capacity)"
            )
        if slot_window < 1:
            raise ValueError("slot_window must be >= 1")
        # warmup() pushes a dummy request of 4 prompt ids +
        # (chunk+1) new tokens through the engine; a legal but
        # tiny --max-len must fail HERE with a clean message, not
        # after the port is bound with a submit() traceback
        if WARMUP_PROMPT_LEN + slot_chunk + 1 > max_len:
            raise ValueError(
                f"max_len must be >= slot_chunk + "
                f"{WARMUP_PROMPT_LEN + 1} (warmup request needs "
                f"{WARMUP_PROMPT_LEN} prompt ids + "
                f"chunk+1={slot_chunk + 1} new tokens; max_len is "
                f"{max_len})"
            )
        # fused K-round windows need a warmup request that rides
        # at least one pure-decode cycle (chunk+2 new tokens); a
        # max_len too tight for that clamps the engine back to
        # one-round dispatches rather than leaving the fused
        # program to compile under a live request behind a 200
        # /health (the no-post-grace-compiles invariant)
        if WARMUP_PROMPT_LEN + slot_chunk + 2 > max_len:
            slot_window = 1
        # --cp composes: long-prompt admissions ring their
        # prefill over the cp mesh's seq axis before joining the
        # pool (the engine runs the same cp_prefill_with_remainder
        # recipe the pod's --sp path does)
        # --prefill-chunk composes (admissions longer than the
        # chunk prefill in pieces) and so does --prefix-cache
        # (admissions with a cached prefix rewind+extend; every
        # admission seeds the cache) — both inside the engine
        self.slot_engine = SlotEngine(
            cfg, params, max_len, slots=slots, chunk=slot_chunk,
            window=slot_window,
            cp_mesh=self.cp_mesh, cp_min_len=self.cp_min_len,
            prefill_chunk=prefill_chunk,
            prefix_cache=self.prefix_cache,
            ledger=self.ledger,
            prefill_floor_s=prefill_floor_s,
        )
        self.slot_window = slot_window
        # prompts longer than this stream through decode_chunk pieces
        # (peak prefill activations O(chunk) instead of O(prompt))
        self.prefill_chunk = prefill_chunk
        self.spec_engine = None
        if draft_layers > 0:
            from ..models.speculative import (
                SpeculativeStepProgram,
                layer_prefix_draft,
            )

            self.draft_params, self.draft_cfg = layer_prefix_draft(
                params, cfg, draft_layers
            )
            # speculative decoding rides the slot engine as a step
            # program (models/stepprog.py): the engine brings
            # queueing/cancel/tracing and the protocol brings
            # multi-token emission per round. One slot, batch 1 —
            # the verify rollback is a per-sequence pos rewind.
            # ledger=None deliberately: the slot engine owns the
            # prefill/decode stamps, and a second stamping authority
            # would fight it.
            self.spec_engine = SlotEngine(
                cfg, params, max_len,
                prefill_chunk=prefill_chunk,
                program=SpeculativeStepProgram(
                    cfg, self.draft_cfg, params, self.draft_params,
                    max_len, speculate=speculate,
                ),
            )
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="inference"
        )
        # serving observability: request/latency/token metrics in a
        # private registry (the supervisor's own /metrics lives on the
        # telemetry server and must not collide in-process)
        from prometheus_client import (
            CollectorRegistry,
            Counter,
            Histogram,
        )

        self._metrics_registry = CollectorRegistry()
        self._m_requests = Counter(
            "containerpilot_serve_requests",
            "requests served, by endpoint and status code",
            ["endpoint", "code"], registry=self._metrics_registry,
        )
        self._m_latency = Histogram(
            "containerpilot_serve_request_seconds",
            "request wall time, by endpoint",
            ["endpoint"], registry=self._metrics_registry,
            buckets=(.005, .02, .05, .1, .25, .5, 1, 2.5, 5, 10, 30, 60),
        )
        self._m_tokens = Counter(
            "containerpilot_serve_generated_tokens",
            "tokens returned by generate/completions (post-trim)",
            registry=self._metrics_registry,
        )
        from ..utils.prom import (
            ensure_build_info,
            ensure_goodput_gauges,
            ensure_loop_lag_gauge,
        )

        ensure_build_info(self._metrics_registry, "replica")
        # the goodput ledger's metrics face: cp_device_seconds_total
        # {stage} + the dispatches/token counter pair
        ensure_goodput_gauges(
            self._metrics_registry, self.ledger, self._decode_counters
        )
        # event-loop health sentinel (analysis/loopcheck.py): one
        # blocking call on this loop stalls every stream, heartbeat,
        # and health check the replica serves — cp_loop_lag_ms is the
        # named form of that stall, gated in the chaos quick suite
        from ..analysis.loopcheck import LoopLagProbe

        self._loop_probe = LoopLagProbe()
        ensure_loop_lag_gauge(self._metrics_registry, self._loop_probe)
        # replica-side request tracing: spans recorded under the
        # gateway's trace id (X-CP-Trace / the mux HEADERS field) —
        # or a freshly minted one for direct clients — retained in a
        # per-server ring on GET /v1/traces, and handed back to the
        # caller as a compact digest (header / final SSE frame) so
        # the gateway stitches a cross-hop timeline without a second
        # RPC. See telemetry/tracing.py.
        self._tracer = tracing.TraceRecorder("replica")
        self._server = HTTPServer()
        # cp-mux/1 accept path (the fleet gateway's multiplexed
        # transport); --no-mux keeps this replica plain HTTP/1.1 and
        # the gateway falls back per-replica
        self._server.mux_enabled = mux
        self._server.route("GET", "/health", self._health)
        self._server.route("GET", "/metrics", self._metrics)
        self._server.route("GET", "/v1/traces", self._traces)
        self._server.route("GET", "/v1/goodput", self._goodput)
        # cold-start collapse seams (fleet/standby.py): the promote
        # verb flips a standby active in one assignment, and the
        # weights endpoint serves this replica's params as a
        # digest-verified chunk stream a launching peer fetches over
        # cp-mux/1 instead of re-reading disk
        self._server.route(
            "POST", "/v3/standby/promote", self._promote_verb
        )
        self._server.route("GET", "/v1/weights", self._weights)
        # disaggregated prefill/decode handoff (kvtier/handoff.py):
        # the prefill verb seeds this replica's prefix cache through
        # the ordinary slot-engine admission; the kv export serves
        # one cached entry as a digest-verified chunk stream; the
        # pull verb fetches an entry from a named peer and injects
        # it into the spill tier for the next request to readmit
        self._server.route("POST", "/v1/prefill", self._prefill_verb)
        self._server.route("POST", "/v1/kv", self._kv_export)
        self._server.route("POST", "/v1/kv/pull", self._kv_pull)
        # drain migration: registered DIRECTLY (not _instrumented)
        # like /v1/kv — a DRAINING replica must still take migration
        # instructions and answer progress queries
        self._server.route("POST", "/v1/migrate", self._migrate_verb)
        route = self._instrumented
        self._server.route("GET", "/v1/model", route(
            "model", self._model_info
        ))
        self._server.route("POST", "/v1/generate", route(
            "generate", self._generate
        ))
        self._server.route("POST", "/v1/score", route(
            "score", self._score
        ))
        # text surface: byte-level tokenizer, zero external assets
        self.tokenizer = None
        if text:
            from .text import ByteTokenizer

            self.tokenizer = ByteTokenizer(cfg.vocab_size)
            self._server.route("POST", "/v1/completions", route(
                "completions", self._completions
            ))
        self._score_fn = None  # jitted lazily; jit caches per length
        # the most rows one request may carry (token rows, ``n``
        # samples, beams): the check on input from outside. Rows past
        # the pool's size queue in the engine like any other request.
        self.max_batch_rows = max_batch_rows

    # -- handlers -------------------------------------------------------

    async def _health(self, _req: Request) -> Response:
        if self.draining:
            # draining ranks above warming: a supervisor health check
            # (or a fleet gateway) must route away NOW even if the
            # model is warm
            return Response(
                503, b"draining\n", headers={"Retry-After": "1"}
            )
        if not self.ready:
            return Response(503, b"warming up\n")
        if self.role == "standby":
            # warm but deliberately not serving: a standby answers
            # health probes honestly (it is NOT taking traffic) while
            # its catalog heartbeat carries role=standby so gateways
            # know it exists. Promotion flips this to 200 instantly.
            return Response(
                503, b"standby\n", headers={"Retry-After": "1"}
            )
        return Response(200, b"ok\n")

    async def _metrics(self, _req: Request) -> Response:
        from ..utils.prom import exposition

        body, content_type = exposition(self._metrics_registry)
        return Response(200, body, content_type=content_type)

    async def _traces(self, req: Request) -> Response:
        """Per-process trace ring: slowest-N + most-recent-N, JSON."""
        return Response(
            200,
            self._tracer.snapshot_json(req.query),
            content_type="application/json",
        )

    def _decode_counters(self):
        """(dispatches, tokens_out) for the goodput surfaces — the
        slot and speculative engines' cumulative pairs summed (each
        engine bumps dispatches once per DEVICE dispatch: one per
        fused window, two per draft+verify round)."""
        dispatches = tokens_out = 0
        for engine in self._engines():
            dispatches += engine.dispatches
            tokens_out += engine.tokens_out
        return dispatches, tokens_out

    def _engines(self) -> List[SlotEngine]:
        """The slot engine and, under --draft-layers, the speculative
        one."""
        if self.spec_engine is None:
            return [self.slot_engine]
        return [self.slot_engine, self.spec_engine]

    async def _goodput(self, _req: Request) -> Response:
        """The device-time ledger, JSON: per-stage seconds (summing
        to uptime by construction), productive fraction, the
        dispatches/token pair, and any detected scheduling gaps —
        requests whose trace says ``slot_queue_wait`` dominated while
        this ledger shows idle seconds inside the same window (free
        capacity the scheduler didn't use). All computed on this read
        path; record paths stay boundary-floats only."""
        from ..telemetry.goodput import goodput_payload

        dispatches, tokens_out = self._decode_counters()
        payload = goodput_payload(
            self.ledger, self._tracer, dispatches, tokens_out,
            role="replica", ready=self.ready, draining=self.draining,
        )
        return Response(
            200, json.dumps(payload).encode(),
            content_type="application/json",
        )

    # -- cold-start collapse surfaces (fleet/standby.py) ---------------

    def promote(self) -> bool:
        """Standby -> active in one assignment: /health flips 200 and
        generate/completions open on the very next request. False
        when this replica is not a promotable standby (already
        active, or draining) — the 409 the HTTP verb answers, and
        the signal the StandbyLauncher uses to drop a contended or
        dying standby and try the next one."""
        if self.role != "standby" or self.draining:
            return False
        self.role = "active"
        log.info("serve: standby promoted to active")
        return True

    async def _promote_verb(self, _req: Request) -> Response:
        """``POST /v3/standby/promote``: the control-plane face of
        ``promote()``. Exactly one promoter wins a race — the second
        call finds role already active and 409s (its caller returns
        the loser to the pool or takes the cold path)."""
        if self.role == "active":
            return Response(409, b"already active\n")
        if self.draining:
            return Response(409, b"draining\n")
        self.promote()
        return Response(
            200,
            json.dumps(
                {"promoted": True, "ready": self.ready}
            ).encode(),
            content_type="application/json",
        )

    async def _ensure_weights_manifest(self):
        """Build (once, executor-wrapped) and cache the transfer
        manifest: leaf/chunk table + digests. Chunk BYTES are not
        cached — they re-derive deterministically at serve time, so
        the server never holds a second full copy of the params."""
        if self._weights_manifest_cache is not None:
            return self._weights_manifest_cache
        if self._weights_lock is None:
            self._weights_lock = asyncio.Lock()
        async with self._weights_lock:
            if self._weights_manifest_cache is None:
                from ..fleet.standby import (
                    encode_manifest,
                    weights_manifest,
                )

                loop = asyncio.get_event_loop()
                manifest = await loop.run_in_executor(
                    None, weights_manifest, self.params
                )
                self._weights_manifest_bytes = encode_manifest(manifest)
                self._weights_manifest_cache = manifest
        return self._weights_manifest_cache

    async def _weights(self, req: Request) -> Response:
        """``GET /v1/weights[?chunk=K]``: this replica's params as a
        length-prefixed manifest followed by digest-verified chunks,
        from flat chunk index K (the resume point after a connection
        death). Served as a close-delimited stream — over cp-mux/1 it
        rides one flow-controlled stream that interleaves with live
        decode traffic. Each leaf is device-fetched on an executor as
        the stream reaches it; the loop never blocks on a transfer."""
        manifest = await self._ensure_weights_manifest()
        try:
            start = int(req.query.get("chunk", ["0"])[0])
        except (ValueError, IndexError):
            return Response(422, b"chunk must be an integer\n")
        chunk_specs = manifest["chunks"]
        if not 0 <= start <= len(chunk_specs):
            return Response(
                422,
                f"chunk must be in [0, {len(chunk_specs)}]\n".encode(),
            )
        from ..fleet.standby import leaf_bytes

        head = self._weights_manifest_bytes
        flat_leaves = jax.tree_util.tree_leaves(self.params)
        loop = asyncio.get_event_loop()

        async def body():
            yield head
            current = -1
            data = b""
            for spec in chunk_specs[start:]:
                if spec["leaf"] != current:
                    current = spec["leaf"]
                    data = await loop.run_in_executor(
                        None, leaf_bytes, flat_leaves[current]
                    )
                yield data[spec["offset"]:spec["offset"] + spec["len"]]

        return StreamingResponse(
            body(), content_type="application/octet-stream"
        )

    # -- disaggregated prefill/decode handoff (kvtier/handoff.py) ------

    async def _prefill_verb(self, req: Request) -> Response:
        """``POST /v1/prefill {"tokens": [[...]]}``: run one prompt
        through the ordinary slot-engine admission path for its SIDE
        EFFECT — the completed prompt's KV lands in the prefix cache
        (and its fingerprint in the next digest beat) — discarding
        the single sampled token. The prefill half of a disaggregated
        handoff: the gateway calls this on the prefill pool, then
        tells the pinned decode replica to pull the entry."""
        if self.prefix_cache is None:
            return Response(
                409, b"prefill handoff needs --prefix-cache\n"
            )
        if self.draining:
            return Response(
                503, b"draining\n", headers={"Retry-After": "1"}
            )
        try:
            body = json.loads(req.body.decode() or "{}")
            tokens, prompt_len = _parse_token_rows(
                body, self.cfg.vocab_size, min_row_len=1
            )
            if len(tokens) != 1:
                raise ValueError("prefill takes a single token row")
            if prompt_len + 1 > self.max_len:
                raise ValueError(
                    f"prompt_len + 1 exceeds max_len {self.max_len}"
                )
        except (ValueError, KeyError, TypeError) as exc:
            return Response(422, f"{exc}\n".encode())
        row = tokens[0]
        fut = self.slot_engine.submit(row, max_new=1)
        await asyncio.wrap_future(fut)
        key = tuple(row)
        pc = self.prefix_cache
        cached = pc.device_entry(key) is not None or (
            pc.spill is not None and pc.spill.peek(key) is not None
        )
        return Response(
            200,
            json.dumps(
                {
                    "ok": True,
                    # False for prompts under the reuse floor — they
                    # can never be reused, so the engine didn't cache
                    # them and there is nothing to hand off
                    "cached": bool(cached),
                    "tokens_prefilled": prompt_len,
                }
            ).encode(),
            content_type="application/json",
        )

    async def _kv_export(self, req: Request) -> Response:
        """``POST /v1/kv[?chunk=K] {"tokens": [[...]]}``: this
        replica's prefix-cache entry for exactly that prompt, as a
        length-prefixed manifest followed by digest-verified chunks
        from flat index K — the weight stream's framing and resume
        discipline (kvtier/handoff.py). 404 when the entry is gone
        from both tiers: the puller returns None and its gateway
        falls back to a local prefill. Serialization (device_get +
        tobytes) runs on an executor; the loop never blocks."""
        pc = self.prefix_cache
        if pc is None:
            return Response(409, b"no prefix cache on this replica\n")
        try:
            body = json.loads(req.body.decode() or "{}")
            tokens, _plen = _parse_token_rows(
                body, self.cfg.vocab_size, min_row_len=1
            )
            if len(tokens) != 1:
                raise ValueError("kv export takes a single token row")
        except (ValueError, KeyError, TypeError) as exc:
            return Response(422, f"{exc}\n".encode())
        try:
            start = int(req.query.get("chunk", ["0"])[0])
        except (ValueError, IndexError):
            return Response(422, b"chunk must be an integer\n")
        if start < 0:
            return Response(422, b"chunk must be >= 0\n")
        key = tuple(tokens[0])
        loop = asyncio.get_event_loop()

        def plan():
            from ..kvtier.handoff import kv_transfer_plan

            cache = pc.device_entry(key)
            if cache is not None:
                host = jax.device_get(cache)
            elif pc.spill is not None:
                # spilled entries are already host numpy — export
                # without waking the device or disturbing the LRU
                host = pc.spill.peek(key)
            else:
                host = None
            if host is None:
                return None
            return kv_transfer_plan(host)

        built = await loop.run_in_executor(None, plan)
        if built is None:
            return Response(404, b"prefix not cached here\n")
        manifest, blobs = built
        chunk_specs = manifest["chunks"]
        if start > len(chunk_specs):
            return Response(
                422,
                f"chunk must be in [0, {len(chunk_specs)}]\n".encode(),
            )
        from ..kvtier.handoff import encode_kv_manifest

        head = encode_kv_manifest(manifest)

        async def stream():
            yield head
            for spec in chunk_specs[start:]:
                yield blobs[spec["leaf"]][
                    spec["offset"]:spec["offset"] + spec["len"]
                ]

        return StreamingResponse(
            stream(), content_type="application/octet-stream"
        )

    async def _kv_pull(self, req: Request) -> Response:
        """``POST /v1/kv/pull {"tokens": [[...]], "from":
        "host:port"}``: fetch that prompt's KV entry from the named
        peer (digest-verified, one redial — kvtier/handoff.py) and
        inject it HOST-side into the spill tier; the next request
        for the prompt readmits it through the same reuse_admission
        path a locally-spilled entry takes. Any failure answers
        non-200 and caches nothing — the gateway falls back to a
        local prefill, so corrupt KV is never served."""
        pc = self.prefix_cache
        if pc is None or pc.spill is None:
            return Response(
                409, b"kv pull needs --prefix-cache and --kv-spill\n"
            )
        try:
            body = json.loads(req.body.decode() or "{}")
            tokens, _plen = _parse_token_rows(
                body, self.cfg.vocab_size, min_row_len=1
            )
            if len(tokens) != 1:
                raise ValueError("kv pull takes a single token row")
            peer = body.get("from", "")
            if not isinstance(peer, str) or ":" not in peer:
                raise ValueError("'from' must be \"host:port\"")
            address, _, port_raw = peer.rpartition(":")
            port = int(port_raw)
            if not address or not 0 < port < 65536:
                raise ValueError("'from' must be \"host:port\"")
        except (ValueError, KeyError, TypeError) as exc:
            return Response(422, f"{exc}\n".encode())
        import time as time_mod

        from ..kvtier.handoff import fetch_kv

        row = tokens[0]
        # a DRAIN-driven pull ("migrate": true) mints a trace so the
        # adoption is findable on this survivor's /v1/traces ring —
        # the gateway never saw this hop, so nobody else records it
        trace = (
            self._tracer.start(None, "kv_migrate")
            if body.get("migrate") else None
        )
        t0 = time_mod.monotonic()
        fetched = await fetch_kv(address, port, row)
        if fetched is None:
            if trace is not None:
                trace.add_span("kv_migrate", t0, time_mod.monotonic())
                trace.finish(502)
            return Response(502, b"kv fetch failed\n")
        host_tree, total_bytes = fetched
        loop = asyncio.get_event_loop()
        adopted = await loop.run_in_executor(
            None, pc.adopt_host, tuple(row), host_tree
        )
        if trace is not None:
            trace.add_span("kv_migrate", t0, time_mod.monotonic())
            trace.finish(200 if adopted else 507)
        if not adopted:
            return Response(
                507, b"kv entry refused (spill budget)\n"
            )
        return Response(
            200,
            json.dumps(
                {
                    "ok": True,
                    "bytes": int(total_bytes),
                    "ms": round(
                        (time_mod.monotonic() - t0) * 1e3, 3
                    ),
                }
            ).encode(),
            content_type="application/json",
        )

    async def _migrate_verb(self, req: Request) -> Response:
        """``POST /v1/migrate``: the drain-migration verb. With
        ``"targets"`` in the body, run an evacuation toward them (the
        operator-drain entry point — the FleetMember drain path calls
        :meth:`migrate_sessions` directly instead); without, answer a
        progress report including the landed fp -> target map, the
        POST-back a gateway or operator polls for completion. Served
        while draining by design — that is exactly when it is used."""
        try:
            body = json.loads(req.body.decode() or "{}")
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, UnicodeDecodeError):
            return Response(422, b"body must be a JSON object\n")
        targets_raw = body.get("targets")
        if targets_raw is None:
            report = dict(self.migration)
            report["landed"] = {
                f"{fp:08x}": tid
                for fp, tid in self._migration_landed.items()
            }
            report["cumulative"] = dict(self._migration_counters)
            return Response(
                200, json.dumps(report).encode(),
                content_type="application/json",
            )
        if self.prefix_cache is None:
            return Response(409, b"migration needs --prefix-cache\n")
        if self.migration["active"]:
            return Response(409, b"migration already running\n")
        from ..kvtier.digest import parse_digest

        try:
            targets = []
            for t in targets_raw:
                _ver, fps = parse_digest(t.get("digest", ""))
                targets.append(
                    (str(t["id"]), str(t["address"]), int(t["port"]),
                     fps)
                )
            window = float(body.get("window_s", 5.0))
        except (KeyError, TypeError, ValueError, AttributeError) as exc:
            return Response(422, f"targets malformed: {exc}\n".encode())
        authority = str(body.get("authority", "")) or (
            f"{self.host}:{self.port}"
        )
        summary = await self.migrate_sessions(
            targets, window_s=window, authority=authority
        )
        return Response(
            200, json.dumps(summary).encode(),
            content_type="application/json",
        )

    def _instrumented(self, endpoint: str, handler):
        """Count + time every API request, under a per-request trace
        (adopting the caller's X-CP-Trace id when present); token
        accounting happens in the handlers themselves (they know the
        post-trim lengths)."""
        import time as time_mod

        async def wrapped(req: Request) -> Response:
            # splice-safe ids only (tracing.safe_id): this id is
            # echoed in answer headers and digests verbatim
            inbound_id = tracing.safe_id(
                req.headers.get("x-cp-trace")
            ) or ""
            if (
                self.draining or self.role == "standby"
            ) and endpoint in ("generate", "completions"):
                # drain rejects NEW decode work only; reads (model,
                # score) stay up for the last consumers of this
                # replica, and everything already admitted runs to
                # completion. A standby refuses the same way: it is
                # warm capacity that has not been promoted — gateways
                # never route here, so this answers only direct
                # probes. The refusal still echoes the caller's
                # trace id — an answered-503 must be findable too.
                # A DRAINING answer is migration-aware: Retry-After
                # tracks evacuation progress, and once this request's
                # prefix has landed on a survivor the header names it
                # so the gateway repoints the pin instead of letting
                # the client re-prefill cold.
                self._m_requests.labels(endpoint, "503").inc()
                headers = {"Retry-After": "1"}
                if self.draining:
                    headers["Retry-After"] = self._drain_retry_after()
                    target = self._drain_migrated_to(req)
                    if target:
                        headers["X-CP-Migrated-To"] = target
                if inbound_id:
                    headers[tracing.TRACE_HEADER] = inbound_id
                body = (
                    b"draining\n" if self.draining else b"standby\n"
                )
                return Response(503, body, headers=headers)
            trace = self._tracer.start(inbound_id or None, endpoint)
            trace.stream_id = tracing.current_stream_id()
            token = tracing.activate(trace)
            t0 = time_mod.perf_counter()
            self._inflight += 1
            try:
                # the hook runs inside the inflight window: a request
                # parked in an injected delay must hold off a drain's
                # inflight==0 wait exactly like one inside the handler
                if self.chaos_hook is not None:
                    await self.chaos_hook(endpoint)
                resp = await handler(req)
            except Exception:
                # the HTTP layer turns this into a 500; the failing
                # (often slowest) requests are exactly what the
                # metrics exist to surface
                trace.finish(500)
                self._m_latency.labels(endpoint).observe(
                    time_mod.perf_counter() - t0
                )
                self._m_requests.labels(endpoint, "500").inc()
                raise
            finally:
                self._inflight -= 1
                tracing.deactivate(token)
            resp.headers.setdefault(
                tracing.TRACE_HEADER, trace.trace_id
            )
            if not isinstance(resp, StreamingResponse):
                trace.finish(resp.status)
                resp.headers.setdefault(
                    tracing.DIGEST_HEADER, trace.digest()
                )
            # else: the stream plumbing owns the trace's tail — it
            # adds the relay span and ships the digest in the final
            # SSE frame (response headers are already gone by then)
            self._m_latency.labels(endpoint).observe(
                time_mod.perf_counter() - t0
            )
            self._m_requests.labels(endpoint, str(resp.status)).inc()
            return resp

        return wrapped

    def _mesh_info(self) -> Optional[Dict[str, int]]:
        """The device mesh the params actually live on (axis -> size),
        None for single-device serving — derived from the shardings,
        so it reports the truth regardless of how loading happened."""
        for leaf in jax.tree_util.tree_leaves(self.params):
            sharding = getattr(leaf, "sharding", None)
            mesh = getattr(sharding, "mesh", None)
            if mesh is not None and mesh.size > 1:
                return {
                    str(name): int(size)
                    for name, size in mesh.shape.items()
                }
        return None

    def _device_info(self) -> Dict[str, Any]:
        """What jax runs this replica on, as jax reports it, and
        where the parameters actually sit: the ids of the devices
        holding parameter shards, and every local device's
        ``bytes_in_use`` (None where the backend keeps no memory
        stats, e.g. the CPU)."""
        devices = jax.devices()
        holders = set()
        for leaf in jax.tree_util.tree_leaves(self.params):
            if isinstance(leaf, jax.Array):
                holders.update(d.id for d in leaf.devices())
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "param_devices": sorted(holders),
            "bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()
            ],
        }

    async def _model_info(self, _req: Request) -> Response:
        body = json.dumps(
            {
                "device": self._device_info(),
                "vocab_size": self.cfg.vocab_size,
                "d_model": self.cfg.d_model,
                "n_heads": self.cfg.n_heads,
                "n_kv_heads": self.cfg.kv_heads,
                "n_layers": self.cfg.n_layers,
                "max_len": self.max_len,
                "mesh": self._mesh_info(),
                # the resident form: float32 as init_params draws it,
                # the compute dtype from serve_cli.load_model
                "weights": resident_weights(self.params),
                "text": self.tokenizer is not None,
                "speculative": (
                    {
                        "draft_layers": self.draft_cfg.n_layers,
                        "speculate": self.speculate,
                        # draft/verify rides the step-program
                        # engine; its dispatch/token counters fold
                        # into the goodput pair
                        "engine": self.spec_engine.stats,
                    }
                    if self.draft_cfg is not None
                    else None
                ),
                "batching": {"max_batch_rows": self.max_batch_rows},
                "prefix_cache": (
                    {
                        "entries": self.prefix_cache.entries,
                        **self.prefix_cache.stats,
                    }
                    if self.prefix_cache is not None
                    else None
                ),
                # cache-aware routing surface: the versioned prefix
                # fingerprint digest (kvtier/digest.py) and the spill
                # tier's accounting; both None when disabled, so the
                # schema is stable across configurations
                "prefix_digest": (
                    self.prefix_cache.digest()
                    if self.prefix_cache is not None else None
                ),
                "kv_spill": (
                    self.prefix_cache.spill.snapshot()
                    if self.prefix_cache is not None
                    and self.prefix_cache.spill is not None
                    else None
                ),
                "slot_engine": self.slot_engine.stats,
                # routed experts of the decode rounds (a held share
                # of them: models/mla_moe.py); None for a dense model
                "experts": self.slot_engine.expert_stats(),
                # generation by diffusion over blocks: the routine and
                # its counters (models/block_diffusion.py); else None
                "diffusion": self.slot_engine.diffusion_stats(),
                # state that is not keys and values: the layer kinds,
                # a row's bytes of it and the steps taken over it
                # (models/hybrid_ssm.py); else None
                "state": self.slot_engine.state_stats(),
                # layers run several times a token: the passes, the
                # planes of keys and values a position holds for them
                # and the passes run so far (models/looped.py); else
                # None
                "loop": self.slot_engine.loop_stats(),
                # a decoder-hybrid-decoder: state, window rings, one
                # plane of keys and values that several layers read,
                # and the counts over them
                # (models/decoder_hybrid.py); else None
                "hybrid_decoder": self.slot_engine.hybrid_decoder_stats(),
                # SSE streaming rides the slot engine's chunks
                "stream": True,
                "draining": self.draining,
                "cp": (
                    {
                        "seq": int(self.cp_mesh.shape["seq"]),
                        "min_len": self.cp_min_len,
                    }
                    if self.cp_mesh is not None else None
                ),
            }
        ).encode()
        return Response(200, body, content_type="application/json")

    def _parse_logit_bias(self, raw: Any) -> Optional[Dict[int, float]]:
        """Delegates to the shared parser (modelcfg.parse_logit_bias)
        so the single-host server and the pod frontend accept exactly
        the same requests."""
        from .modelcfg import parse_logit_bias

        return parse_logit_bias(raw, self.cfg.vocab_size)

    def _parse_stops(self, raw: Any) -> List[List[int]]:
        """Delegates to the shared parser (modelcfg.parse_stop_ids)
        so the single-host server and the pod frontend accept exactly
        the same stop sequences."""
        from .modelcfg import parse_stop_ids

        return parse_stop_ids(raw, self.cfg.vocab_size)

    def _parse_sampling(
        self, body: Dict[str, Any], tokens: List[List[int]],
        prompt_len: int, default_eos: int = -1,
    ) -> Dict[str, Any]:
        """Validate the sampling/decode knobs shared by /v1/generate
        and /v1/completions. Raises ValueError for a 422."""
        p = {
            "max_new_requested": int(body.get("max_new_tokens", 16)),
            "temperature": float(body.get("temperature", 0.0)),
            "seed": int(body.get("seed", 0)),
            "top_k": int(body.get("top_k", 0)),
            "top_p": float(body.get("top_p", 0.0)),
            "eos_id": int(body.get("eos_id", default_eos)),
            "min_new": int(body.get("min_new_tokens", 0)),
            "presence": float(body.get("presence_penalty", 0.0)),
            "frequency": float(body.get("frequency_penalty", 0.0)),
            "logprobs": bool(body.get("logprobs", False)),
            "beam_width": int(body.get("beam_width", 0)),
            "length_penalty": float(body.get("length_penalty", 0.0)),
            "stop": self._parse_stops(body.get("stop")),
            "logit_bias": self._parse_logit_bias(
                body.get("logit_bias")
            ),
        }
        if p["logit_bias"] and p["beam_width"]:
            raise ValueError("logit_bias does not apply to beam search")
        p["n"] = int(body.get("n", 1))
        if not 1 <= p["n"] <= self.max_batch_rows:
            raise ValueError(
                f"n must be in [1, --max-batch-rows "
                f"{self.max_batch_rows}]"
            )
        if p["n"] > 1:
            if len(tokens) != 1:
                raise ValueError(
                    "n > 1 takes a single prompt row (it IS the "
                    "row multiplier)"
                )
            if p["beam_width"]:
                raise ValueError(
                    "n does not compose with beam search (beams "
                    "already return one best row)"
                )
        if p["beam_width"]:
            from ..models.beam import validate_beam_args

            if p["temperature"] > 0.0 or p["top_k"] or p["top_p"]:
                raise ValueError(
                    "beam search is deterministic; drop "
                    "temperature/top_k/top_p"
                )
            validate_beam_args(self.cfg, len(tokens), p["beam_width"])
            if p["beam_width"] > self.max_batch_rows:
                # beams tile the KV cache: one request must not exceed
                # the server's configured device-row budget
                raise ValueError(
                    f"beam_width capped at --max-batch-rows "
                    f"({self.max_batch_rows})"
                )
        if (not 0 <= p["top_k"] <= self.cfg.vocab_size
                or not 0.0 <= p["top_p"] <= 1.0):
            raise ValueError(
                f"top_k must be in [0, vocab {self.cfg.vocab_size}] "
                "and top_p in [0, 1]"
            )
        if p["eos_id"] >= self.cfg.vocab_size:
            raise ValueError(f"eos_id must be < vocab {self.cfg.vocab_size}")
        if not 0 <= p["min_new"] <= max(p["max_new_requested"], 0):
            raise ValueError(
                "min_new_tokens must be in [0, max_new_tokens]"
            )
        if p["min_new"] and p["beam_width"]:
            raise ValueError(
                "min_new_tokens does not apply to beam search"
            )
        if not (abs(p["presence"]) <= 100 and abs(p["frequency"]) <= 100):
            raise ValueError(
                "presence/frequency penalties must be in [-100, 100]"
            )
        if (p["presence"] or p["frequency"]) and p["beam_width"]:
            raise ValueError(
                "penalties do not apply to beam search"
            )
        if prompt_len + p["max_new_requested"] > self.max_len:
            raise ValueError(
                f"prompt_len + max_new_tokens exceeds max_len "
                f"{self.max_len}"
            )
        if p["max_new_requested"] < 1:
            raise ValueError("max_new_tokens must be >= 1")
        refuse = getattr(
            getattr(self.cfg, "family", None), "refuse_request", None)
        if refuse is not None:
            refuse(p)  # what this family's decode routine does not take
        return p

    def _beam(
        self, tokens: List[List[int]], p: Dict[str, Any]
    ) -> List[List[int]]:
        """One beam search, on the inference executor thread."""
        from ..models.beam import beam_search

        # beam search is NOT prefix-consistent: the best 16-token
        # beam's first 6 tokens are not the best 6-token continuation,
        # so the compiled horizon is the REQUESTED length (beams are
        # explicit requests; the compile churn is theirs)
        out, _score = beam_search(
            self.params, jnp.asarray(tokens, jnp.int32), self.cfg,
            max_new_tokens=p["max_new_requested"],
            max_len=self.max_len, beam_width=p["beam_width"],
            eos_id=p["eos_id"], length_penalty=p["length_penalty"],
            prefill_chunk=self.prefill_chunk,
        )
        return [jax.device_get(out).tolist()]

    async def _dispatch_generate(
        self, tokens: List[List[int]], p: Dict[str, Any]
    ) -> List[List[int]]:
        """Run a validated generate request and return its generated
        rows, in order. Three arms, each the only code that serves
        its input: beam search (a one-shot call: beams are outside
        the step-program protocol), the speculative engine (greedy,
        one row, nothing that reshapes the logits, under
        --draft-layers), and for everything else the slot engine, one
        row at a time. Both engines' emission is already eos-capped
        and exact in max_new (the _trim downstream is idempotent on
        it), and both stamp request-boundary timings the trace
        converts to slot_queue_wait/prefill/decode spans — batched,
        nothing recorded per token."""
        trace = tracing.current_trace()
        if p["beam_width"]:
            t0 = tracing.now()
            try:
                return await asyncio.get_event_loop().run_in_executor(
                    self._executor, self._beam, tokens, p
                )
            finally:
                if trace is not None:
                    trace.add_span("compute", t0, tracing.now())
        timings: List[Optional[Dict[str, float]]] = [
            {} if trace is not None else None for _ in tokens
        ]
        if (
            self.spec_engine is not None
            and p["temperature"] <= 0.0
            and p["min_new"] == 0
            and not p["presence"] and not p["frequency"]
            and not p["logit_bias"]
            and len(tokens) == 1
        ):
            # draft-and-verify through the speculative step program:
            # byte-identical to plain greedy decode
            futures = [self.spec_engine.submit(
                tokens[0], p["max_new_requested"],
                eos_id=p["eos_id"], seed=p["seed"],
                timings=timings[0],
            )]
        else:
            # each row joins the running chunk loop at the next
            # boundary and draws from fold_in(PRNGKey(seed), i); rows
            # beyond the pool's free slots queue like any request
            futures = [
                self._submit_row(row, p, i, timings=timings[i])
                for i, row in enumerate(tokens)
            ]
        rows = list(await asyncio.gather(
            *[asyncio.wrap_future(fut) for fut in futures]
        ))
        if trace is not None:
            # the request is as slow as its last row: that row's
            # stamps are the request's stages (stages never overlap)
            tracing.add_engine_spans(
                trace, max(timings, key=lambda t: t.get("done", 0.0))
            )
        return rows

    def _submit_row(
        self, row: List[int], p: Dict[str, Any], row_idx: int = 0,
        **hooks: Any,
    ):
        """One sequence of a validated request into the slot engine;
        ``hooks`` are submit's on_tokens / cancel / timings."""
        return self.slot_engine.submit(
            row, p["max_new_requested"],
            temperature=p["temperature"], top_k=p["top_k"],
            top_p=p["top_p"], eos_id=p["eos_id"], seed=p["seed"],
            row=row_idx, min_new=p["min_new"],
            presence_penalty=p["presence"],
            frequency_penalty=p["frequency"],
            logit_bias=p["logit_bias"], **hooks,
        )

    @staticmethod
    def _trim(
        generated: List[List[int]], max_new_requested: int, eos_id: int
    ) -> List[List[int]]:
        generated = [r[:max_new_requested] for r in generated]
        if eos_id >= 0:
            # trim each row at its first eos (inclusive); the model
            # emitted pad beyond it anyway
            generated = [
                row[: row.index(eos_id) + 1] if eos_id in row else row
                for row in generated
            ]
        return generated

    @staticmethod
    def _trim_stops(
        generated: List[List[int]], stops: List[List[int]]
    ) -> List[List[int]]:
        """Cut each row at the earliest occurrence of any stop
        sequence, EXCLUDING the stop itself (the OpenAI convention).
        Decode still ran to its compiled length — static shapes — so
        this is response shaping, not an early exit."""
        if not stops:
            return generated
        out = []
        for row in generated:
            cut = len(row)
            for stop in stops:
                n = len(stop)
                for i in range(0, min(cut, len(row) - n + 1)):
                    if row[i:i + n] == stop:
                        cut = min(cut, i)
                        break
            out.append(row[:cut])
        return out

    async def _generate(self, req: Request) -> Response:
        try:
            body = json.loads(req.body.decode() or "{}")
            tokens, prompt_len = _parse_token_rows(
                body, self.cfg.vocab_size, min_row_len=1
            )
            p = self._parse_sampling(body, tokens, prompt_len)
            stream = bool(body.get("stream", False))
            if p["n"] > 1:
                if stream:
                    # the client sent ONE row; blame the actual
                    # conflict, not the post-duplication row count
                    raise ValueError(
                        "n does not compose with stream (one SSE "
                        "stream carries one row)"
                    )
                # OpenAI's n: one prompt, n independent samples. Each
                # duplicated row draws from fold_in(seed, i) — the
                # server's per-row key convention — so the samples
                # differ under temperature (greedy duplicates are
                # identical by definition).
                tokens = [list(tokens[0]) for _ in range(p["n"])]
            if stream:
                return self._generate_stream(tokens, p)
        except (ValueError, KeyError, TypeError) as exc:
            return Response(422, f"{exc}\n".encode())

        generated = await self._dispatch_generate(tokens, p)
        generated = self._trim(generated, p["max_new_requested"], p["eos_id"])
        generated = self._trim_stops(generated, p["stop"])
        self._m_tokens.inc(sum(len(r) for r in generated))
        payload: Dict[str, Any] = {"tokens": generated}
        if p["logprobs"]:
            loop = asyncio.get_event_loop()
            payload["logprobs"] = await loop.run_in_executor(
                self._executor, self._echo_logprobs, tokens, generated
            )
        return Response(
            200,
            json.dumps(payload).encode(),
            content_type="application/json",
        )

    def _generate_stream(
        self, tokens: List[List[int]], p: Dict[str, Any]
    ) -> "StreamingResponse":
        """SSE token streaming over the slot engine's chunk
        boundaries: each emitted delta becomes a ``data:`` event, the
        terminal event carries ``done``; concatenating the deltas
        byte-matches the non-streamed response's row (the engine's
        emission IS the post-trim output). A client disconnect sets
        the cancel event — the engine frees the slot at the next
        chunk boundary instead of decoding to the end."""
        if len(tokens) != 1:
            raise ValueError("stream serves a single row per request")
        return self._stream_response(tokens[0], p)

    def _stream_response(
        self,
        row: List[int],
        p: Dict[str, Any],
        delta_event=None,
        tail_events=None,
    ) -> "StreamingResponse":
        """Shared slot-engine SSE plumbing for the token and text
        streaming surfaces. ``delta_event(delta) -> dict`` shapes each
        event; ``tail_events() -> [dict]`` may append events before
        the terminal ``done`` (e.g. a UTF-8 decoder flush)."""
        for knob, why in (
            ("logprobs", "echo logprobs need the full row"),
            ("beam_width", "beams have no incremental prefix"),
            ("stop", "stop sequences need whole-row trimming"),
        ):
            if p[knob]:
                raise ValueError(f"stream does not compose with "
                                 f"{knob} ({why})")
        if delta_event is None:
            delta_event = lambda d: {"tokens": d}  # noqa: E731
        if tail_events is None:
            tail_events = list  # noqa: E731 — no tail

        import threading as threading_mod

        loop = asyncio.get_event_loop()
        deltas: "asyncio.Queue" = asyncio.Queue()
        _DONE = object()
        cancel = threading_mod.Event()

        def on_tokens(delta: List[int]) -> None:  # worker thread
            loop.call_soon_threadsafe(deltas.put_nowait, delta)

        # the trace outlives the handler's contextvar window (the
        # relay runs after the handler returned), so the stream
        # plumbing holds the object directly
        trace = tracing.current_trace()
        timings: Optional[Dict[str, float]] = (
            {} if trace is not None else None
        )
        fut = self._submit_row(
            row, p, on_tokens=on_tokens, cancel=cancel, timings=timings
        )
        fut.add_done_callback(
            lambda _f: loop.call_soon_threadsafe(deltas.put_nowait, _DONE)
        )

        sent = [0]
        finished = [False]
        first_delta_at = [0.0]

        def finish() -> None:
            # runs on ANY stream end — completion, mid-stream
            # disconnect (generator finally), or a disconnect so
            # early the generator never started (StreamingResponse
            # close callback). Idempotent: both paths may fire.
            if finished[0]:
                return
            finished[0] = True
            cancel.set()  # the engine stops decoding this row
            self._m_tokens.inc(sent[0])
            if trace is not None:
                _finish_stream_trace()

        def _finish_stream_trace() -> None:
            # span conversion happens ONCE, here: engine boundary
            # stamps plus the relay window, then the trace files into
            # the ring (status 200 — an abandoned stream delivered
            # what it delivered; transport failure has no status)
            tracing.add_engine_spans(trace, timings)
            if first_delta_at[0]:
                trace.add_span(
                    "stream_relay", first_delta_at[0], tracing.now(),
                    events=sent[0],
                )
            trace.finish(200)

        def sse(payload: Dict[str, Any]) -> bytes:
            return b"data: " + json.dumps(payload).encode() + b"\n\n"

        async def events():
            try:
                while True:
                    delta = await deltas.get()
                    if delta is _DONE:
                        break
                    if trace is not None and not first_delta_at[0]:
                        first_delta_at[0] = tracing.now()
                    sent[0] += len(delta)
                    yield sse(delta_event(delta))
                for extra in tail_events():
                    yield sse(extra)
                done: Dict[str, Any] = {"done": True, "count": sent[0]}
                if trace is not None:
                    # the final frame is the stream's digest channel
                    # (response headers are long gone): the gateway
                    # splices these spans into its own timeline
                    finish()
                    done["trace"] = trace.trace_id
                    done["spans"] = trace.digest()
                yield sse(done)
            finally:
                finish()

        return StreamingResponse(events(), close=finish)

    async def _completions(self, req: Request) -> Response:
        """Text in/out over the built-in byte-level tokenizer: encode
        the prompt, run the exact same decode dispatch as
        /v1/generate, decode the generated ids back to text. eos
        defaults to the tokenizer's EOS so generation stops naturally;
        pass "eos_id": -1 to disable. "stop" takes STRINGS here (a
        single string or a list); they are byte-encoded and applied
        as token-level stop sequences, excluded from the output."""
        try:
            body = json.loads(req.body.decode() or "{}")
            prompt = body.get("prompt")
            if not isinstance(prompt, str) or not prompt:
                raise ValueError("'prompt' must be a non-empty string")
            row = self.tokenizer.encode(prompt)
            if len(row) >= self.max_len:
                raise ValueError(
                    f"prompt encodes to {len(row)} ids; max_len is "
                    f"{self.max_len}"
                )
            from .modelcfg import parse_stop_strings

            stop_raw = parse_stop_strings(body.pop("stop", None))
            if stop_raw is not None:
                body["stop"] = [
                    self.tokenizer.encode(s, bos=False)
                    for s in stop_raw
                ]
            p = self._parse_sampling(
                body, [row], len(row), default_eos=self.tokenizer.EOS
            )
            if p["n"] > 1:
                raise ValueError(
                    "n returns token rows; use /v1/generate"
                )
            if bool(body.get("stream", False)):
                return self._completions_stream(row, p)
        except (ValueError, KeyError, TypeError) as exc:
            return Response(422, f"{exc}\n".encode())

        generated = await self._dispatch_generate([row], p)
        generated = self._trim(generated, p["max_new_requested"], p["eos_id"])
        generated = self._trim_stops(generated, p["stop"])
        self._m_tokens.inc(len(generated[0]))
        return Response(
            200,
            json.dumps(
                {
                    "text": self.tokenizer.decode(generated[0]),
                    "tokens": generated[0],
                }
            ).encode(),
            content_type="application/json",
        )

    def _completions_stream(
        self, row: List[int], p: Dict[str, Any]
    ) -> "StreamingResponse":
        """Text SSE over the same slot-chunk plumbing: each event
        carries the delta's ids AND the text they decode to, with
        UTF-8 partial-byte holdback (text.stream_decoder).
        Concatenated event text equals the non-streamed ``text``;
        concatenated ids equal its ``tokens``."""
        from .text import stream_decoder

        delta_event, tail_events = stream_decoder(self.tokenizer)
        return self._stream_response(
            row, p, delta_event=delta_event, tail_events=tail_events
        )

    def _ensure_score_fn(self) -> None:
        if self._score_fn is not None:
            return
        from .modelcfg import score_logprobs_fn

        self._score_fn = jax.jit(score_logprobs_fn(self.cfg))

    def _echo_logprobs(
        self,
        prompts: List[List[int]],
        generated: List[List[int]],
    ) -> List[List[float]]:
        """Per-token logprobs of the TRIMMED generated ids, via one
        teacher-forced pass over prompt+generated. Decode is bit-equal
        to the forward (tested invariant), so these are exactly the
        probabilities the sampler saw — and the approach works
        uniformly across every decode path (slots, speculative, beam)
        with no decode changes. With --kv-int8 the
        echo is approximate (the scorer runs full-precision while
        decode read a quantized KV cache; parity there is ~5e-2, not
        bitwise). Rows pad to a 16-multiple width (capped at max_len)
        so arbitrary trimmed lengths cannot compile a fresh scoring
        program per request — causal attention makes the extra pad
        positions free."""
        self._ensure_score_fn()
        rows = [p + g for p, g in zip(prompts, generated)]
        width = min(-(-max(len(r) for r in rows) // 16) * 16,
                    self.max_len)
        padded = [r + [0] * (width - len(r)) for r in rows]
        picked = jax.device_get(
            self._score_fn(self.params, jnp.asarray(padded, jnp.int32))
        ).astype(float)
        out: List[List[float]] = []
        for row_lp, prompt, gen in zip(picked, prompts, generated):
            # lp[i] scores token i+1 of the padded row; generated
            # token j sits at padded index len(prompt)+j
            start = len(prompt) - 1
            out.append([
                round(float(x), 6)
                for x in row_lp[start:start + len(gen)]
            ])
        return out

    async def _score(self, req: Request) -> Response:
        """Teacher-forced per-token logprobs of the given sequences —
        the standard scoring/perplexity endpoint (no sampling)."""
        try:
            body = json.loads(req.body.decode() or "{}")
            tokens, row_len = _parse_token_rows(
                body, self.cfg.vocab_size, min_row_len=2
            )
            if row_len > self.max_len:
                raise ValueError(f"row length exceeds max_len {self.max_len}")
        except (ValueError, KeyError, TypeError) as exc:
            return Response(422, f"{exc}\n".encode())

        self._ensure_score_fn()

        def run() -> Any:
            toks = jnp.asarray(tokens, jnp.int32)
            picked = self._score_fn(self.params, toks)
            picked = jax.device_get(picked).astype(float)
            return picked

        loop = asyncio.get_event_loop()
        picked = await loop.run_in_executor(self._executor, run)
        return Response(
            200,
            json.dumps(
                {
                    "logprobs": [[round(float(x), 6) for x in row]
                                 for row in picked],
                    "sums": [round(float(row.sum()), 6) for row in picked],
                }
            ).encode(),
            content_type="application/json",
        )

    # -- lifecycle ------------------------------------------------------

    @property
    def inflight(self) -> int:
        """Requests still being served: handler-held requests plus
        slot-engine rows still decoding (a streamed generation's
        handler returns immediately; its row lives in the engine).
        The double count while a buffered request waits on its slot
        future only makes drain-waiting conservative."""
        n = self._inflight
        for engine in self._engines():
            stats = engine.stats
            n += stats["active"] + stats["queued"]
        return n

    @property
    def occupancy(self) -> float:
        """Fraction of decode capacity in use, the autoscaling
        signal: (active + queued slot-engine rows) / slots, so queued
        work pushes it past 1.0 — a replica can be *over*-subscribed,
        and a scaler must see that."""
        stats = self.slot_engine.stats
        return (stats["active"] + stats["queued"]) / stats["slots"]

    def kv_note(self) -> str:
        """The ``kv=`` heartbeat field's VALUE (the name is owned by
        ``fleet/notes.py``): the prefix cache's reuse counters,
        ``hits,misses,tokens_reused,spilled,readmitted``. Empty
        without a prefix cache, so fleets that don't reuse pay zero
        note bytes."""
        pc = self.prefix_cache
        if pc is None:
            return ""
        s = pc.stats
        return (
            f"{s['hits']},{s['misses']},{s['tokens_reused']},"
            f"{s['spilled']},{s['readmitted']}"
        )

    def prefix_digest_note(self) -> str:
        """The ``pd=`` heartbeat field's value: the prefix
        fingerprint digest the gateway's cache-aware routing scores
        against. Empty without a prefix cache or before the first
        digest build."""
        pc = self.prefix_cache
        if pc is None:
            return ""
        return pc.digest() or ""

    def goodput_note(self) -> str:
        """The device-time ledger's heartbeat field (``gp=`` —
        cumulative per-stage seconds + the dispatches/token pair),
        appended by FleetMember the same duck-typed way ``kv_note``
        is. Always present: a replica with zero reuse still has a
        badput story to tell, and the gateway's fleet ledger must
        fold in every member from its very first beat."""
        dispatches, tokens_out = self._decode_counters()
        return self.ledger.note(dispatches, tokens_out)

    # -- drain migration ------------------------------------------------

    async def migrate_sessions(
        self,
        targets: List[Any],
        window_s: float = 5.0,
        authority: str = "",
    ) -> Dict[str, Any]:
        """Evacuate this replica's cached prefixes to the survivors
        before a drain deregisters it: plan deterministically
        (kvtier.plan_migration — digest-coldest target, fp-family
        affinity, warm fps land with zero bytes), then push each cold
        entry inside the bounded window by POSTing a pull instruction
        at its target (the handoff wire in reverse; the target
        ``fetch_kv``s from ``authority`` — this replica's advertised
        host:port — and adopts via the same ``reuse_admission`` path).
        Every failure is a COUNTED fallback to today's re-prefill
        behavior, never an error: a dead target or poisoned chunk
        bumps ``failed``, window expiry bumps ``timeout`` for each
        un-pushed entry, and the drain proceeds regardless.

        ``targets`` is a list of ``(instance_id, address, port,
        fingerprint_set)`` tuples (a survivor's advertised ``pd=``
        digest, parsed). Returns the migration summary dict."""
        import time as time_mod

        pc = self.prefix_cache
        m = self.migration
        if pc is None or not targets or m["active"]:
            return dict(m)
        from ..kvtier.handoff import plan_migration, push_kv

        loop = asyncio.get_event_loop()
        # rows still on their way to the host tier land first: the
        # enumeration reads the cache for good
        await loop.run_in_executor(None, pc.flush)
        keys = await loop.run_in_executor(None, pc.export_keys)
        plan = plan_migration(
            keys, [(t[0], t[3]) for t in targets]
        )
        addr = {t[0]: (t[1], int(t[2])) for t in targets}
        m.update(
            active=True, total=len(plan), done=0, failed=0,
            timeout=0, window_s=float(window_s),
            started_at=time_mod.monotonic(),
        )
        self._migration_counters["total"] += len(plan)
        deadline = m["started_at"] + max(0.0, float(window_s))
        bytes_moved = 0
        try:
            for entry in plan:
                if time_mod.monotonic() >= deadline:
                    left = m["total"] - m["done"] - m["failed"]
                    m["timeout"] += left
                    self._migration_counters["timeout"] += left
                    log.warning(
                        "serve: migrate window expired with %d "
                        "entries unmoved", left,
                    )
                    break
                if entry["warm"]:
                    # already warm on the survivor: landed with zero
                    # bytes moved, but the pin still repoints
                    m["done"] += 1
                    self._migration_counters["done"] += 1
                    self._record_landing(entry["fp"], entry["target"])
                    continue
                host, port = addr[entry["target"]]
                got = await push_kv(
                    host, port, list(entry["key"]), authority,
                    read_timeout=max(
                        1.0, deadline - time_mod.monotonic()
                    ),
                )
                if got is None:
                    m["failed"] += 1
                    self._migration_counters["failed"] += 1
                else:
                    bytes_moved += got
                    m["done"] += 1
                    self._migration_counters["done"] += 1
                    self._record_landing(entry["fp"], entry["target"])
        finally:
            m["active"] = False
        summary = dict(m)
        summary["bytes"] = bytes_moved
        log.info(
            "serve: migration moved %d/%d entries (%d bytes, "
            "%d failed, %d timed out)",
            m["done"], m["total"], bytes_moved, m["failed"],
            m["timeout"],
        )
        return summary

    def _record_landing(self, fp: int, target: str) -> None:
        landed = self._migration_landed
        landed[fp] = target
        landed.move_to_end(fp)
        while len(landed) > 256:
            landed.popitem(last=False)

    def migrate_note(self) -> str:
        """The ``mg=`` heartbeat field's value (the name is owned by
        ``fleet/notes.py``): cumulative migration counters plus the
        most recent fp -> target landings, which the gateway uses to
        repoint sticky pins as sessions land. Empty until a
        migration has ever run — replicas that never drain pay zero
        note bytes."""
        c = self._migration_counters
        if not c["total"] and not self.migration["active"]:
            return ""
        from ..kvtier.digest import encode_migration_note

        landed = list(self._migration_landed.items())
        landed.reverse()  # most-recent-first survives truncation
        return encode_migration_note(
            c["done"], c["total"], c["failed"], c["timeout"],
            bool(self.migration["active"]), landed,
        )

    def _drain_retry_after(self) -> str:
        """Retry-After for a drain 503, derived from migration
        progress: the observed per-entry pace extrapolated over what
        is left, capped by the remaining window — a polite-retry
        client comes back right as its session lands warm instead of
        after a fixed beat."""
        import time as time_mod

        m = self.migration
        if not m["active"] or m["total"] <= 0:
            return "1"
        elapsed = max(0.0, time_mod.monotonic() - m["started_at"])
        settled = m["done"] + m["failed"]
        if settled <= 0:
            remaining = float(m["window_s"])
        else:
            remaining = elapsed * (m["total"] - settled) / settled
        remaining = min(
            remaining, max(0.0, float(m["window_s"]) - elapsed)
        )
        return str(max(1, min(30, int(remaining + 0.999))))

    def _drain_migrated_to(self, req: Request) -> str:
        """The survivor instance id this 503'd request's prefix has
        already landed on, or "" — advertised in X-CP-Migrated-To so
        the gateway repoints the pin instead of re-prefilling cold.
        Tolerant: any unparseable body simply gets no header."""
        if not self._migration_landed:
            return ""
        from ..kvtier.digest import prefix_fingerprint

        try:
            body = json.loads(req.body.decode() or "{}")
            rows = body.get("tokens")
            if (isinstance(rows, list) and rows
                    and isinstance(rows[0], list)):
                row = [int(t) for t in rows[0]]
            elif (self.tokenizer is not None
                  and isinstance(body.get("prompt"), str)):
                row = self.tokenizer.encode(body["prompt"])
            else:
                return ""
            fp = prefix_fingerprint(row)
        except (ValueError, TypeError, AttributeError,
                UnicodeDecodeError):
            return ""
        if fp is None:
            return ""
        return self._migration_landed.get(fp, "")

    def enter_maintenance(self) -> None:
        """Start draining: health 503, new generate/completions 503 +
        Retry-After, in-flight work (including running slot-engine
        rows) finishes. Idempotent."""
        if not self.draining:
            log.info("serve: entering maintenance (draining)")
            # ledger: from here until exit, every second is drain
            # badput — capacity leaving the fleet, the in-flight rows
            # it still finishes included (they are the drain's cost)
            self.ledger.set_override("drain")
        self.draining = True

    def exit_maintenance(self) -> None:
        """Stop draining and accept traffic again. Idempotent."""
        if self.draining:
            log.info("serve: exiting maintenance")
            self.ledger.clear_override()
        self.draining = False

    def _warmup_fingerprint(self) -> str:
        """The warm-bucket marker key: everything that shapes this
        server's warmup program set (modelcfg.warmup_fingerprint)."""
        from .modelcfg import warmup_fingerprint

        engine = self.slot_engine
        return warmup_fingerprint(
            self.cfg, self.max_len,
            slots=engine.slots,
            slot_chunk=engine.chunk,
            # the fused window K shapes the engine's compiled program
            # set: a marker written at K=1 must never skip the fused
            # program a K=4 launch needs (PR 13's compile-cache skip
            # stays correct only if K is part of the identity)
            slot_window=engine.window,
            # ...and so do the read lengths its decode programs come in
            read_ladder=engine.read_ladder,
            draft_layers=(
                self.draft_cfg.n_layers
                if self.draft_cfg is not None else 0
            ),
            speculate=self.speculate,
            mesh=self._mesh_info(),
        )

    def compile_cache_note(self) -> str:
        """The ``cc=`` heartbeat field's value (the name is owned by
        ``fleet/notes.py``): this replica's compile-cache
        dir + warm-marker digest, so readers see when the warm set
        moved. Computed ONCE at warmup end (the
        marker only changes there) and cached — a heartbeat must
        never pay marker file I/O on the serving loop. Empty without
        a cache dir — fleets not sharing a cache pay zero note
        bytes."""
        return self._compile_cache_note

    async def warmup(self) -> None:
        """Compile the programs requests run before reporting healthy:
        one dummy request through the slot engine and, under
        --draft-layers, the speculative programs and one through that
        engine.

        Requests with other prompt lengths still compile their prefill
        on first use (shapes are static). With a shared compile cache
        dir configured, buckets a previous same-shaped process already
        marked warm are SKIPPED — the XLA disk cache holds their
        executables, so the first live request pays a fast cache load
        instead of a compile, and this launch's ``compile_warmup``
        seconds collapse to near zero (the cold-start-collapse
        lever)."""
        # ledger: everything from here until ready flips — XLA
        # compiles AND the dummy slot-engine request driving them —
        # is compile_warmup, stamped via an override so the engine's
        # own prefill/decode boundary stamps can't claim it. Costed
        # BEFORE /health goes 200: the very first scrape of a
        # scale-up replica already shows its compile badput.
        self.ledger.set_override("compile_warmup")
        # chaos seam: an injected slow boot parks HERE, inside the
        # compile_warmup attribution window — the fault the standby
        # pool exists to mask
        if self.chaos_hook is not None:
            await self.chaos_hook("warmup")
        loop = asyncio.get_event_loop()
        fingerprint = ""
        # buckets are "slots" and "spec"; a marker from a build that
        # also listed the one-shot programs' "p4"/"p16" reads the same
        warm: set = set()
        if self.compile_cache_dir:
            from .modelcfg import load_warm_buckets

            fingerprint = self._warmup_fingerprint()
            warm = await loop.run_in_executor(
                None, load_warm_buckets,
                self.compile_cache_dir, fingerprint,
            )
        # the decode programs come in a ladder of read lengths, and a
        # warm-up request reaches the first only: the step program
        # compiles them side by side and runs each once on the idle
        # pool (models/stepprog.py warm_ladder). Marked warm or not: a
        # rung that met its first dispatch under traffic would stall a
        # window on a compile, or on the cache's load of one. First,
        # so that the request below finds its two programs made
        await asyncio.wrap_future(self.slot_engine.warm_programs())
        buckets = {"slots"}
        if "slots" not in warm:
            # one dummy request through the engine compiles its whole
            # program set (standalone prefill, first-sample, insert,
            # the (S, chunk) chunk program and — with window > 1 —
            # the fused (S, chunk, K) window: max_new = chunk+2
            # leaves one token past the admission round, so the
            # second cycle dispatches fused) so the first live
            # request doesn't stall on multi-second compilation
            # behind a 200 /health
            engine = self.slot_engine
            # (a program whose step is not one token says itself
            # how many new tokens reach the fused window)
            warm_new = getattr(engine.program, "warm_new", 0) or (
                engine.chunk + (2 if engine.window > 1 else 1)
            )
            fut = engine.submit(
                [0] * WARMUP_PROMPT_LEN, max_new=warm_new,
            )
            await asyncio.wrap_future(fut)
        if self.spec_engine is not None:
            buckets.add("spec")
            if "spec" not in warm:
                await self._warm_speculative()
        if self.compile_cache_dir:
            from .modelcfg import (
                compile_cache_note,
                mark_warm_buckets,
            )

            await loop.run_in_executor(
                None, mark_warm_buckets,
                self.compile_cache_dir, fingerprint, buckets,
            )
            # the advertisement heartbeats will carry from now on —
            # digested off the marker just written, off-loop, once
            self._compile_cache_note = await loop.run_in_executor(
                None, compile_cache_note, self.compile_cache_dir
            )
        # warmup attribution closes here, and the serving clock opens
        # in ``idle`` — both before ready flips, so no wall-second
        # between "compiled" and "first scrape" is misattributed
        self.ledger.clear_override()
        self.ledger.enter("idle")
        self.ready = True
        log.info(
            "serve: default shapes warm%s; %s",
            " (marker-skipped)" if "slots" in warm else "",
            "standing by" if self.role == "standby"
            else "accepting traffic",
        )

    async def _warm_speculative(self) -> None:
        """The DEFAULT path for greedy traffic under --draft-layers:
        one shared rule for which per-k draft/verify programs must
        compile inside the grace (models/speculative.py), then one
        dummy generation for the engine's admission glue."""
        from ..models.speculative import warm_speculative

        await asyncio.get_event_loop().run_in_executor(
            self._executor, warm_speculative,
            self.params, self.draft_params, self.cfg,
            self.draft_cfg, self.speculate, self.max_len,
        )
        spec_new = min(
            self.speculate + 2, self.max_len - WARMUP_PROMPT_LEN
        )
        if spec_new >= 1:
            fut = self.spec_engine.submit(
                [0] * WARMUP_PROMPT_LEN, max_new=spec_new,
            )
            await asyncio.wrap_future(fut)

    async def run(self) -> None:
        await self._server.start_tcp(self.host, self.port)
        self.port = self._server.bound_port or self.port
        self._loop_probe.start()
        log.info("serve: listening on %s:%d", self.host, self.port)
        await self.warmup()

    async def stop(self) -> None:
        self.ledger.freeze()
        self._loop_probe.stop()
        for engine in self._engines():
            # joins the worker thread; run off-loop so in-flight
            # dispatches can't block the event loop
            await asyncio.get_event_loop().run_in_executor(
                None, engine.stop
            )
        await self._server.stop()

    async def abort(self) -> None:
        """Test-only (chaos harness): die like SIGKILL. The listener
        and every live connection drop FIRST — in-flight clients see
        resets, exactly as if the process vanished — and only then are
        the decode threads reaped so the test process doesn't leak
        them. No drain, no deregistration: a FleetMember's catalog
        record is left to decay critical by TTL expiry, which is the
        crash signature gateways must route around."""
        self.ready = False
        self.ledger.freeze()
        self._loop_probe.stop()
        await self._server.abort()
        for engine in self._engines():
            await asyncio.get_event_loop().run_in_executor(
                None, engine.stop
            )


if __name__ == "__main__":
    raise SystemExit(main())
