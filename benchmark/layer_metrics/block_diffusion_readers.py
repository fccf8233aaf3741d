"""Shared by the readers of the block-diffusion family
(``denoise_forward_device_ms.block-diffusion``,
``denoise_forward_roofline.block-diffusion``, ``tokens_per_row_forward``,
``block_attention_roofline``, ``denoise_sample_share``).

The unit is a POOL FORWARD: every slot's block through the model once.
The ``sample`` scope runs exactly once a pool forward inside the
``jit_run`` programs, so decode_programs.py's ``token_steps`` counts
pool forwards for this family (what it calls a token-step is one pass
of every layer over the pool), and mla_moe_readers.py's ``scoped``,
``step_ms`` and ``per_step_ms`` are per pool forward here. A forward
reveals several tokens of a row's block or none, so nothing here is
per token.

From the counters: what ``/v1/model`` ``diffusion`` (by LIVE row) and
``experts`` (every row of the pool) moved by between the window's two
snapshots. A program without them (any before PR 35, any other
family) gives None, and so do the readers."""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from benchmark.harness.spec import load_module

HERE = os.path.dirname(__file__)
readers = load_module(os.path.join(HERE, "mla_moe_readers.py"))
scopes = readers.scopes
programs = readers.programs

COUNTERS = ("row_forwards", "tokens_revealed", "blocks_committed",
            "commit_forwards")


def forward_ms(run: Dict[str, Any]) -> Optional[float]:
    """Device ms of the decode programs per pool forward."""
    return readers.step_ms(run)


def scope_ms(run: Dict[str, Any], *children: str) -> Optional[float]:
    """Device ms per pool forward under the named innermost scopes."""
    return readers.per_step_ms(run, *children)


def diffusion(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Deltas of the ``diffusion`` counters over the window, summed
    over replicas, with the routine as it stands."""
    if "after" not in run:
        return None
    total: Dict[str, Any] = {}
    for after, before in zip(run["after"]["model"], run["before"]["model"]):
        a, b = after.get("diffusion"), before.get("diffusion")
        if not a or not b:
            return None
        for key in COUNTERS:
            total[key] = total.get(key, 0) + a[key] - b[key]
        total["block_length"] = a["block_length"]
        total["denoising_steps"] = a["denoising_steps"]
    out = scopes.artefact_dir(run)
    if "cell" in run and os.path.isdir(out):
        # beside the run's other artefacts, for PERF.md's breakdown
        with open(os.path.join(out, "diffusion_counters.json"), "w") as fh:
            json.dump(total, fh)
    return total if total.get("row_forwards") else None


def positions_per_forward(run: Dict[str, Any]) -> int:
    """Positions the pool forwards at once: slots x block_length."""
    return readers.slots(run) * int(run["config"]["diffusion"]["block_length"])


def touched_per_forward(run: Dict[str, Any]) -> Optional[float]:
    """Experts (over all layers) that got at least one token, per pool
    forward: ``rows`` counts positions x layers a forward."""
    counted = readers.experts(run)
    rows = positions_per_forward(run) * run["config"]["num_hidden_layers"]
    if not counted or not rows:
        return None
    forwards = counted["rows"] / rows
    return counted["expert_steps_touched"] / forwards if forwards else None


def live_positions(run: Dict[str, Any]) -> Optional[float]:
    """The slots' live contexts added up (prompt plus half the output
    of the window's finished requests, times the slots)."""
    context = readers.live_context(run)
    return readers.slots(run) * context if context else None
