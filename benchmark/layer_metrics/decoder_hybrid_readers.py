"""Shared by the readers of the decoder-hybrid-decoder family
(``decode_step_device_ms.decoder-hybrid``,
``decode_step_roofline.decoder-hybrid``, ``shared_plane_roofline``,
``window_ring_roofline``, ``ssm_state_roofline.mamba1``,
``decode_gmu_share``).

From the trace (``scoped``): inside the slot engine's decode programs
(``jit_run``), self seconds by the INNERMOST scope of an operation's
path among ``ssm.*``, ``attn.*``, ``mlp.*`` and ``gmu`` (the family
opens ``attn.window``, ``attn.full`` and ``attn.cross`` around a
layer's write and read of its keys and values, ``attn.diff`` around the
difference of the two maps and its norm, ``ssm.update`` around a decode
step's recurrence, ``gmu`` around a gated memory unit), the token-steps
of the traced window (the executions of ``sample``) and the programs'
device seconds.

The live rows of a step are the mean of the ``live`` argument of the
``engine.dispatch`` events INSIDE the traced window (admission_spans.py
``live_of``), where the times are: not the 30 s counters' mean, which
opens on an empty pool (the two-windows fault of the hybrid state-space
and looped readers is not copied). A live row's context is the prompt
plus half the output of the window's finished requests.

The family is told by its ``/v1/model`` ``hybrid_decoder`` block; a
program without it (any before PR 45, any other family) gives None, and
so do the readers."""
from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional

from benchmark.harness import counts_decoder_hybrid as counts
from benchmark.harness import peaks
from benchmark.harness.spec import load_module

HERE = os.path.dirname(__file__)
readers = load_module(os.path.join(HERE, "mla_moe_readers.py"))
spans = load_module(os.path.join(HERE, "admission_spans.py"))
scopes = readers.scopes
programs = readers.programs

_CHILD = re.compile(r"(?:ssm|attn|mlp)\.[A-Za-z_]\w*|(?<![\w.])gmu(?![\w.])")


COUNTERS = ("ssm_row_steps", "ring_row_steps", "ring_rows_wrapped",
            "shared_plane_reads", "prefill_positions_self",
            "prefill_positions_cross")


def block(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The first replica's ``hybrid_decoder`` block at the window's
    close; None for any other family. Kept beside the run's other
    artefacts (``hybrid_decoder.json``: the block, and under
    ``moved_in_window`` what its counters moved by between the two
    snapshots)."""
    if "_decoder_hybrid_block" in run:
        return run["_decoder_hybrid_block"]
    models = (run.get("after") or {}).get("model") or [{}]
    found = models[0].get("hybrid_decoder")
    run["_decoder_hybrid_block"] = found
    out = scopes.artefact_dir(run) if found and "cell" in run else ""
    if os.path.isdir(out):
        before = ((run.get("before") or {}).get("model") or [{}])[0].get(
            "hybrid_decoder") or {}
        moved = {name: found[name] - before.get(name, 0) for name in COUNTERS}
        with open(os.path.join(out, "hybrid_decoder.json"), "w") as fh:
            json.dump({**found, "moved_in_window": moved}, fh)
    return found


def scoped(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """{"children": {scope: seconds}, "steps": n, "decode_s": s} of
    the decode programs, averaged over the device planes; None where
    there is no trace, no step loop or no ``hybrid_decoder`` block."""
    if "_decoder_hybrid_scoped" in run:
        return run["_decoder_hybrid_scoped"]
    found = None
    doc = scopes.xplane_of(run) if block(run) else None
    steps = programs.token_steps(run) if doc is not None else 0
    if steps:
        lo, hi = scopes.window_of(run)
        children: Dict[str, float] = {}
        for plane in doc["planes"]:
            program_of = programs.program_finder(plane["modules"])
            inside = [op for op in plane["ops"] if program_of(op[1])]
            for _name, path, self_ns in readers._self_ns(inside, lo, hi):
                child = _CHILD.findall(path or "")
                if child:
                    children[child[-1]] = children.get(child[-1], 0.0) + self_ns / 1e9
        planes = len(doc["planes"])
        found = {
            "children": {k: v / planes for k, v in children.items()},
            "steps": steps,
            "decode_s": programs.decode_seconds(run["trace"]),
        }
        out = scopes.artefact_dir(run)
        if "cell" in run and os.path.isdir(out):
            # beside the run's other artefacts, for PERF.md's breakdown
            with open(os.path.join(out, "decoder_hybrid_scopes.json"), "w") as fh:
                json.dump({**found, "hybrid_decoder": block(run),
                           "live": live(run)}, fh)
    run["_decoder_hybrid_scoped"] = found
    return found


def step_ms(run: Dict[str, Any]) -> Optional[float]:
    """Device ms of the decode programs per token-step."""
    found = scoped(run)
    if not found or not found["decode_s"]:
        return None
    return found["decode_s"] * 1e3 / found["steps"]


def per_step_ms(run: Dict[str, Any], *children: str) -> Optional[float]:
    """Device ms per token-step under the named innermost scopes."""
    found = scoped(run)
    if not found:
        return None
    seconds = sum(found["children"].get(c, 0.0) for c in children)
    return seconds * 1e3 / found["steps"] if seconds > 0 else None


def share(run: Dict[str, Any], *children: str) -> Optional[float]:
    """Self time under the named scopes as a share of the decode
    programs' device time, in percent."""
    found = scoped(run)
    if not found or not found["decode_s"]:
        return None
    part = sum(found["children"].get(c, 0.0) for c in children)
    return 100.0 * part / found["decode_s"] if part > 0 else None


def live(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """{"rows": live rows of a step inside the traced window,
    "context": a live row's mean context}; None without the block, a
    trace whose dispatches carry ``live``, or a finished request."""
    if "_decoder_hybrid_live" not in run:
        found = None
        rows = spans.live_rows(run) if block(run) else None
        context = readers.live_context(run)
        if rows and rows["with_live"] and context:
            found = {"rows": rows["mean"], "context": context}
        run["_decoder_hybrid_live"] = found
    return run["_decoder_hybrid_live"]


def roofline(run: Dict[str, Any], least_bytes: float,
             took_ms: Optional[float]) -> Optional[float]:
    """``least_bytes`` at the chip's peak bytes/s over ``took_ms``, in
    percent."""
    if not took_ms:
        return None
    least_ms = least_bytes / peaks.peak(
        run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / took_ms


def step_bytes(run: Dict[str, Any]) -> Optional[float]:
    counted = live(run)
    if not counted:
        return None
    return counts.decode_step_bytes(
        run["config"], counted["rows"], counted["context"])
