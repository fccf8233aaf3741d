"""What both launchers do before they hand over to the program's own
``main()``.

The supervisor's job ``exec`` names ``benchmark/launch/replica.py`` or
``trainer.py`` in place of ``python -m containerpilot_tpu.workload.*``.
The launcher runs IN the process that holds the chip, because three
things the benchmark's contract asks for exist only there:

(a) the one width the program's flags cannot express: ``derive_d_ff``
    (3 x d_model) is made to return the configuration's published
    ``intermediate_size``. Nothing else of the program is changed.
(b) a control thread that watches a directory the harness writes
    into: ``trace-start`` -> ``jax.profiler.start_trace``,
    ``trace-stop`` -> ``stop_trace``, ``stats`` -> device facts and
    ``peak_bytes_in_use``. Each command is answered with
    ``<command>.done`` holding a JSON object.
(c) a ``jax.monitoring`` listener that counts backend compiles (and
    cache loads of a new program) after the ``window-open`` command.

(d) for a trainer, an observer around the compiled step (see
    ``observe_train_steps``): it reads, and changes nothing.

The harness's parent never imports jax; no other process touches the
chip while this one lives.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List

POLL_S = 0.02
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _write_json(path: str, obj: Any) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w") as fh:
        json.dump(obj, fh)
    os.replace(tmp, path)


def override_d_ff(config: Dict[str, Any], modules: List[Any]) -> None:
    """Make ``derive_d_ff`` return the configuration's width in every
    module that holds the name. Refuses any d_model but the
    configuration's, so the override cannot leak to another model."""
    d_model = int(config["hidden_size"])
    d_ff = int(config["intermediate_size"])

    def derive_d_ff(width: int) -> int:
        if int(width) != d_model:
            raise SystemExit(
                f"benchmark launcher: d_ff override is for d_model "
                f"{d_model}, the program asked for {width}"
            )
        return d_ff

    was = modules[0].derive_d_ff(d_model)
    for module in modules:
        module.derive_d_ff = derive_d_ff
    print(
        f"benchmark launcher: derive_d_ff({d_model}) {was} -> {d_ff} "
        f"(the configuration's intermediate_size; no flag gives it)",
        flush=True,
    )


SAMPLE_ABOVE = 1 << 20  # elements; a larger leaf is read in part
SAMPLE_SHARE = 16       # ... its leading 1/16 along the first free axis


def _leaves(tree: Any):
    """(name, leaf, stacked over layers?) with the program's names:
    ``embed``, ``layers/wq``, ..."""
    import jax

    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
        yield name, leaf, name.startswith("layers/")


def _first_moment(tree: Any) -> Dict[str, Dict[str, List[float]]]:
    """Per leaf, and per layer of a stacked leaf, the 2-norm and the
    plain sum of its elements, reduced ON the device: nothing but
    these numbers leaves it. (A norm hardly moves with rounding noise,
    which adds to it in the square; a sum moves with it in the first
    order, so it is the number a lower precision shows in.)"""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def reduce(x, stacked):
        # the whole reduction in one program: no copy of the leaf is made
        axes = tuple(range(1, x.ndim)) if stacked else None
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)), jnp.sum(x, axis=axes)

    out: Dict[str, Dict[str, List[float]]] = {"norms": {}, "sums": {}}
    for name, leaf, stacked in _leaves(tree):
        norms, sums = jax.device_get(reduce(leaf, stacked))
        out["norms"][name] = [float(v) for v in norms.reshape(-1)]
        out["sums"][name] = [float(v) for v in sums.reshape(-1)]
    return out


def _sampled(tree: Any) -> Dict[str, Any]:
    """A host copy of each leaf, of a large leaf only its leading
    1/SAMPLE_SHARE along the first axis that is not the layer's: the
    parameters' change is read on that part (the reference reads the
    same part), so 0.2 GB and not 2.8 GB cross to the host, and the
    device holds one leaf's part at a time (33 MB at most)."""
    import jax

    out = {}
    for name, leaf, stacked in _leaves(tree):
        axis = 1 if stacked else 0
        keep = leaf.shape[axis]
        if leaf.size > SAMPLE_ABOVE:
            keep = max(keep // SAMPLE_SHARE, 1)
        part = leaf[:, :keep] if stacked else leaf[:keep]
        out[name] = (jax.device_get(part), stacked)
    return out


def observe_train_steps(parallel: Any, control_dir: str, steps: int) -> None:
    """Wrap ``parallel.make_train_step`` so that the step it returns is
    watched through its first ``steps`` calls, for ``correct``: the
    first moment of the optimizer after call 1 (the first gradient as
    AdamW got it, times 1 - b1) as one norm and one sum per leaf and
    layer, and the parameters' change after call ``steps`` (on a fixed
    part of each leaf, see ``_sampled``) as one norm each, written to
    ``<control_dir>/train_observed.json``. It reads, and keeps nothing
    on the device (a part of one leaf at a time while it is copied),
    so the device's peak stays the program's to within 0.06 GB; after
    ``steps`` calls the wrapper only forwards."""
    import jax
    import numpy as np

    make = parallel.make_train_step

    def make_observed(*args: Any, **kwargs: Any):
        step = make(*args, **kwargs)
        seen: Dict[str, Any] = {"calls": 0}

        def observed(state, tokens):
            n = seen["calls"]
            if n >= steps:
                return step(state, tokens)
            if n == 0:
                seen["shape"] = list(tokens.shape)
                seen["before"] = _sampled(state.params)  # the state is donated
            state, loss = step(state, tokens)
            seen["calls"] = n + 1
            if n == 0:
                moments = [s for s in jax.tree_util.tree_leaves(
                    state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                    if hasattr(s, "mu")]
                seen["first_moment"] = _first_moment(moments[0].mu)
            if n + 1 == steps:
                before, change = seen.pop("before"), {}
                for name, (after, stacked) in _sampled(state.params).items():
                    diff = (after - before[name][0]).astype(np.float64)
                    rows = diff.reshape(diff.shape[0], -1) if stacked \
                        else diff.reshape(1, -1)
                    change[name] = [float(np.sqrt(np.sum(r * r))) for r in rows]
                _write_json(os.path.join(control_dir, "train_observed.json"), {
                    "steps": steps, "tokens_shape": seen["shape"],
                    "first_moment_norms": seen["first_moment"]["norms"],
                    "first_moment_sums": seen.pop("first_moment")["sums"],
                    "change_norms": change,
                })
            return state, loss

        return observed

    parallel.make_train_step = make_observed
    print(f"benchmark launcher: observing the first {steps} calls of the "
          "train step (reads the state, changes nothing)", flush=True)


def device_facts() -> Dict[str, Any]:
    import jax

    devices = jax.local_devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_stats": [d.memory_stats() for d in devices],
    }


class Control:
    """The command directory. One thread, started before the program's
    main(); it dies with the process (daemon)."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self._window_open = threading.Event()
        self._lock = threading.Lock()
        self.compiles: List[Dict[str, Any]] = []
        self.compiles_before = 0
        self._tracing = False
        os.makedirs(directory, exist_ok=True)

    # -- (c) compiles after the window opened ---------------------------

    def on_duration(self, event: str, duration: float, **_kw: Any) -> None:
        if event != COMPILE_EVENT:
            return
        with self._lock:
            if self._window_open.is_set():
                self.compiles.append(
                    {"at": time.time(), "seconds": duration}
                )
            else:
                self.compiles_before += 1

    # -- (b) commands ----------------------------------------------------

    def _handle(self, name: str, arg: str) -> Dict[str, Any]:
        import jax

        if name == "window-open":
            self._window_open.set()
            return {"at": time.time()}
        if name == "trace-start":
            t0 = time.time()
            jax.profiler.start_trace(arg)
            self._tracing = True
            return {"called": t0, "at": time.time()}
        if name == "trace-stop":
            t0 = time.time()
            if self._tracing:
                jax.profiler.stop_trace()
                self._tracing = False
            return {"at": t0, "returned": time.time()}
        if name == "stats":
            with self._lock:
                compiles = list(self.compiles)
                before = self.compiles_before
            return {
                **device_facts(), "compiles_in_window": compiles,
                "compiles_before_window": before, "at": time.time(),
            }
        return {"error": f"unknown command {name!r}"}

    def _loop(self) -> None:
        while True:
            try:
                names = sorted(os.listdir(self.directory))
            except OSError:
                names = []
            for name in names:
                if "." in name:
                    continue  # answers and temporaries
                path = os.path.join(self.directory, name)
                try:
                    with open(path) as fh:
                        arg = fh.read().strip()
                    os.remove(path)
                except OSError:
                    continue
                try:
                    answer = self._handle(name, arg)
                except Exception as exc:  # report, never kill the server
                    answer = {"error": f"{type(exc).__name__}: {exc}"}
                _write_json(os.path.join(self.directory, f"{name}.done"),
                            answer)
            time.sleep(POLL_S)

    def start(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration
        )
        threading.Thread(
            target=self._loop, name="benchmark-control", daemon=True
        ).start()


def prepare(argv: List[str]):
    """Parse ``<config.json> <control-dir> -- <program args>``, put the
    repo root on the path, install (b) and (c), write the first device
    facts. Returns (config, program argv)."""
    if len(argv) < 3 or argv[2] != "--":
        raise SystemExit(
            "usage: launcher <config.json> <control-dir> -- <args>"
        )
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    if root not in sys.path:
        sys.path.insert(0, root)
    with open(argv[0]) as fh:
        config = json.load(fh)
    control = Control(argv[1])
    control.start()
    # what jax runs on, before anything is built: the harness ends the
    # run here when it is not the platform the cell asks for
    _write_json(os.path.join(argv[1], "device.json"), device_facts())
    return config, argv[3:]
