"""Runs a configuration's plain reference in a process of its own:
``python benchmark/harness/refchild.py <spec.json> <out.json>``.

Started by the harness only after the supervised tree has exited, so
the chip is free and ``memory_peak_bytes`` stays the program's. The
reference module is the one the configuration file names (beside it
under ``benchmark/configs/``); this runner imports jax and that
module, nothing of the program.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time


def main(argv) -> int:
    t0 = time.monotonic()
    with open(argv[0]) as fh:
        spec = json.load(fh)
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache:
        jax.config.update("jax_compilation_cache_dir", spec["compile_cache"])
    # every program, the small ones too: a new process would compile
    # each again, and dozens of them cost seconds of every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = jax.devices()[0]
    if device.platform != spec["platform"]:
        print(f"reference child: jax runs on {device.platform!r}, "
              f"not {spec['platform']!r}", file=sys.stderr)
        return 3
    module_spec = importlib.util.spec_from_file_location(
        "benchmark_reference", spec["reference"]
    )
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    with open(spec["config_path"]) as fh:
        config = json.load(fh)
    result = getattr(module, spec["check"])(config, spec)
    result["device"] = {"platform": device.platform, "kind": device.device_kind}
    result["seconds"] = time.monotonic() - t0
    with open(argv[1], "w") as fh:
        json.dump(result, fh)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
