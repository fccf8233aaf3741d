"""Shared by the decode-step readers: which programs of the device
trace are the slot engine's decode programs, and how many token-steps
they ran inside the traced window.

The chunk and the fused-window programs are both jitted from a
function named ``run`` (models/slots.py), so the trace's module line
names them ``jit_run(<fingerprint>)``. A token-step is one pass of
every layer over the whole slot pool. Inside those programs the steps
are ``while`` loops nested in each other (rounds > the chunk's steps >
the layer stack); the trace has one event per execution of each. The
token-step is the most frequent loop that still holds at least half of
the decode programs' device time (smaller loops, e.g. inside the
sampler, run more often but hold almost none of it). Steps are
therefore counted from the trace itself, on the profiler's clock, not
from a counter read over HTTP at the window's edges. Seen by hand in
PR 23's first trace: 7 dispatches, 55 ``while.60`` of 43 ms, each
holding the four layers' operations.

Nothing in the serving path carries a ``jax.named_scope`` yet
(ROADMAP S2), so this rests on names the compiler made: ``jit_run`` and
the opcode ``while``. Named scopes replace it in the tracing issue."""

DECODE_MODULE = "jit_run"


def decode_seconds(trace):
    return sum(m["seconds"] for name, m in trace["modules"].items()
               if name.startswith(DECODE_MODULE))


def token_steps(trace):
    floor = decode_seconds(trace) / 2
    counts = [count for count, inclusive in trace["loops"].values()
              if inclusive >= floor]
    return max(counts) if counts else 0
