"""Layer: slot engine. The reader of admit_sync_ms_per_admission.py on the same
artefacts, for an open loop whose tails carry no bound: its cell reports
``serve_tokens_per_s`` end to end where the closed loops report
``tpot_p95_ms``, and a per-layer metric names ONE end-to-end metric that
every cell it lists reports (PERF.md section 2)."""
import os

from benchmark.harness.spec import load_module

read = load_module(os.path.join(os.path.dirname(__file__), "admit_sync_ms_per_admission.py")).read
