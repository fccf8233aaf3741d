"""A builder's tool for the chip (no test collects it): does the chip's
compiler keep a float32 -> bfloat16 -> float32 round trip inside one
jitted computation? Three forms: a jit's output (a reference's weight
recipe), mid-computation, and the body of a scan over a float32 state
(granite_reference.py's bf16-state control), each beside
``jax.lax.reduce_precision(x, 8, 7)``.
  chiprun --chips 1 -- python3 benchmark/tests/chip_round_trip.py
prints one JSON line and writes chiprun_out/round_trip.json:
``*_representable`` is the share of values that are bfloat16's (1.0:
rounded; about 1e-5: the rounding was dropped), ``scan_*_y_moved_max``
how far the rounding moved the read through the state (0.0: dropped).
PR 37's reading on a v5e: every cast form dropped, every
reduce_precision kept (PERF.md section 6)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np


def cast(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def reduce(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def representable(x):
    x = np.asarray(x)
    return float(np.mean((x.view(np.uint32) & 0xFFFF) == 0))


def scan_with(rounding):
    def run(decay, push):
        def one(state, inputs):
            d, p = inputs
            state = d[:, None] * state + p
            if rounding is not None:
                state = rounding(state)
            return state, jnp.sum(state, axis=-1)
        last, y = jax.lax.scan(one, jnp.zeros(push.shape[1:]), (decay, push))
        return last, y
    return jax.jit(run)


def main():
    key = jax.random.PRNGKey(7)
    out = {"device": str(jax.devices()[0])}
    draw = lambda rounding: jax.jit(
        lambda k: rounding(jax.random.normal(k, (512, 1024), jnp.float32) * 0.0156))
    out["draw_cast_representable"] = representable(draw(cast)(key))
    out["draw_reduce_representable"] = representable(draw(reduce)(key))
    out["draw_cast_equals_reduce"] = bool(jnp.array_equal(draw(cast)(key), draw(reduce)(key)))
    # the same cast, in the middle of a float32 computation
    mid = lambda rounding: jax.jit(lambda x: rounding(x * 1.5) * 2.0)
    x = jax.random.normal(key, (512, 1024), jnp.float32)
    out["mid_cast_representable"] = representable(mid(cast)(x) / 2.0)
    out["mid_reduce_representable"] = representable(mid(reduce)(x) / 2.0)
    k1, k2 = jax.random.split(key)
    decay = jnp.exp(-jax.random.uniform(k1, (600, 128), jnp.float32, 0.01, 0.8))
    push = jax.random.normal(k2, (600, 128, 256), jnp.float32) * 0.05
    plain_last, plain_y = scan_with(None)(decay, push)
    for name, rounding in (("cast", cast), ("reduce", reduce)):
        last, y = scan_with(rounding)(decay, push)
        out[f"scan_{name}_state_representable"] = representable(last)
        out[f"scan_{name}_y_moved_max"] = float(jnp.max(jnp.abs(y - plain_y)))
    out["scan_plain_state_representable"] = representable(plain_last)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/round_trip.json", "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))


main()
