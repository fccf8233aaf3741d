"""The one traffic generator. A mix is a data file
(``benchmark/traffic/<name>.json``) of parameters; this reads its
``kind`` and makes the requests (or the training tokens) from
``--seed``. Ideas copied from ``containerpilot_tpu/chaos/trace.py``
(tenants, sessions, multi-turn growth, modulated-Poisson bursts,
lognormal lengths, length snapping), resized and re-zeroed:

* every seed gets the SAME set of sizes (lengths are fixed quantiles of
  the lognormal). A closed loop takes them in a seeded, stratified
  order. An open loop takes the whole schedule (instants, which session
  speaks, every length) from the mix's own ``pattern_seed``, and the
  seed makes only the token ids: its tails hang on which long prompt
  meets which burst, and six seeds that reshuffle that spread by 15 %
  where two runs of one schedule agree (PR 23), so the seed must not
  change the work;
* prompt lengths come only from the mix's listed shapes, because the
  server compiles one prefill program per prompt length.

Kinds: ``closed`` (N clients, each sends its next request when the
last one ended), ``open`` (requests are due on a schedule whether or
not earlier ones finished), ``train`` (token shards for the trainer's
``--data-dir``).
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Dict, Iterator, List


@dataclass
class Request:
    index: int
    tokens: List[int]
    max_new_tokens: int
    due_s: float = 0.0
    session_id: str = ""
    #: tokens of the prompt this request shares with an earlier one of
    #: its session or tenant (what a prefix cache could reuse)
    shared_tokens: int = 0
    extra: Dict[str, Any] = field(default_factory=dict)

    def payload(self) -> Dict[str, Any]:
        body: Dict[str, Any] = {
            "tokens": [self.tokens],
            "max_new_tokens": self.max_new_tokens,
            "temperature": 0.0,
            "stream": True,
        }
        if self.session_id:
            body["session_id"] = self.session_id
        return body


def _rng(seed: int, salt: str) -> random.Random:
    return random.Random(f"{salt}:{seed}")


def lognormal_lengths(n: int, spec: Dict[str, Any]) -> List[int]:
    """``n`` fixed quantiles of a capped lognormal (median, sigma, min,
    max), optionally snapped UP to a multiple of ``quantum``: the same
    multiset for every seed."""
    normal = NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        value = spec["median"] * math.exp(spec["sigma"] * z)
        value = int(round(value))
        quantum = int(spec.get("quantum", 0))
        if quantum > 0:
            value = -(-value // quantum) * quantum
        out.append(max(int(spec["min"]), min(int(spec["max"]), value)))
    return out


def _ids(rng: random.Random, n: int, vocab: int) -> List[int]:
    return [rng.randrange(1, vocab) for _ in range(n)]


# -- closed loop --------------------------------------------------------


def closed_requests(traffic: Dict[str, Any], vocab: int, seed: int) -> Iterator[Request]:
    """An endless stream for a closed loop: unshared random prompts of
    the listed lengths, output lengths from the pool's fixed quantiles
    in a seeded order. The order is STRATIFIED: the sorted pool is cut
    into ``block`` strata, and every run of ``block`` consecutive
    requests holds one length from each stratum (which one, and in what
    order, the seed decides). Any stretch of the stream, such as the
    part a window of fixed length gets through, then holds nearly the
    same mix of short and long requests whatever the seed."""
    rng = _rng(seed, "closed")
    pool = int(traffic["pool"])
    block = int(traffic.get("block", 1))
    if pool % block:
        raise ValueError(f"pool {pool} is not a multiple of block {block}")
    outputs = sorted(lognormal_lengths(pool, traffic["output"]))
    per = pool // block
    strata = [outputs[i * per:(i + 1) * per] for i in range(block)]
    prompt_lens = list(traffic["prompt"]["lens"])
    index = 0
    while True:
        for stratum in strata:
            rng.shuffle(stratum)
        for turn in range(per):
            lengths = [stratum[turn] for stratum in strata]
            rng.shuffle(lengths)
            for max_new in lengths:
                plen = prompt_lens[index % len(prompt_lens)]
                yield Request(index=index, tokens=_ids(rng, plen, vocab),
                              max_new_tokens=max_new)
                index += 1


# -- open loop ----------------------------------------------------------


def arrival_pattern(traffic: Dict[str, Any], duration_s: float) -> List[float]:
    """Modulated-Poisson arrival instants over ``duration_s`` from the
    mix's own ``pattern_seed``: exponential gaps, ``burst_factor``
    times denser inside bursts (exponential dwell times in either
    state). A short run of such a process holds a number of arrivals
    that swings widely, so the pattern is stretched to hold exactly
    ``round(rate_rps * duration_s)`` of them: the rate offered is the
    rate written in the file, the bursts keep their shape."""
    a = traffic["arrivals"]
    rng = _rng(int(a.get("pattern_seed", 0)), "arrivals")
    quiet, burst = float(a["quiet_dwell_s"]), float(a["burst_dwell_s"])
    factor = float(a["burst_factor"])
    count = int(round(float(a["rate_rps"]) * duration_s))
    if count < 1:
        return []
    # quiet rate such that the time-weighted mean is rate_rps
    base = float(a["rate_rps"]) * (quiet + burst) / (quiet + factor * burst)
    now, in_burst = 0.0, False
    state_until = rng.expovariate(1.0 / quiet)
    instants: List[float] = []
    while len(instants) <= count:
        now += rng.expovariate(base * (factor if in_burst else 1.0))
        while now > state_until:
            in_burst = not in_burst
            state_until += rng.expovariate(1.0 / (burst if in_burst else quiet))
        instants.append(now)
    stretch = duration_s / instants[count]
    return [t * stretch for t in instants[:count]]


def open_schedule(
    traffic: Dict[str, Any], vocab: int, seed: int, duration_s: float
) -> List[Request]:
    """Every request due inside ``duration_s``, sorted by due time.

    The schedule's shape is the same for every seed (see the module's
    note); the seed makes the token ids.
    With ``sessions`` in the mix, each arrival is the next turn of a
    live session: the session least recently served among those whose
    last turn is at least ``think_floor_s`` old (a new arrival finds
    none only when every session is thinking; it then starts one
    early). A session that has had its turns is replaced by a fresh
    one of the same tenant. Turn k+1's prompt is turn k's prompt plus
    ``turn_growth`` new ids (a simulated reply and new user text: the
    replica never checks history against what it generated, and the
    schedule must be a pure function of the seed)."""
    rng = _rng(seed, "open")  # token ids only
    instants = arrival_pattern(traffic, duration_s)
    if not instants:
        return []
    # every size and every choice below comes from the mix's own seed
    shape = _rng(int(traffic["arrivals"].get("pattern_seed", 0)), "shape")
    n = len(instants)
    outputs = lognormal_lengths(n, traffic["output"])
    shape.shuffle(outputs)
    requests: List[Request] = []
    sess = traffic.get("sessions")
    if not sess:
        prompts = lognormal_lengths(n, traffic["prompt"]) if "median" in traffic[
            "prompt"] else [
            traffic["prompt"]["lens"][i % len(traffic["prompt"]["lens"])]
            for i in range(n)
        ]
        shape.shuffle(prompts)
        for i, due in enumerate(instants):
            requests.append(Request(
                index=i, tokens=_ids(rng, prompts[i], vocab),
                max_new_tokens=outputs[i], due_s=due,
            ))
        return requests

    tenants = int(sess["tenants"])
    system = [_ids(rng, int(sess["system_prompt_len"]), vocab)
              for _ in range(tenants)]
    live = tenants * int(sess["sessions_per_tenant"])
    turns = int(sess["turns"])
    floor = float(sess["think_floor_s"])
    growth = int(sess["turn_growth"])
    max_prompt = int(sess["max_prompt"])
    # first-turn user lengths: one fixed multiset, as many as sessions
    # can start in the run
    first_lens = lognormal_lengths(max(n, live), sess["first_turn"])
    shape.shuffle(first_lens)
    started = 0

    def fresh(slot: int) -> Dict[str, Any]:
        nonlocal started
        tenant = slot % tenants
        user = first_lens[started % len(first_lens)]
        state = {
            "id": f"t{tenant}-s{started}", "tenant": tenant, "turn": 0,
            "history": system[tenant] + _ids(rng, user, vocab),
            "shared": len(system[tenant]), "last": -1e9,
        }
        started += 1
        return state

    sessions = [fresh(slot) for slot in range(live)]
    for i, due in enumerate(instants):
        ready = [s for s in sessions if due - s["last"] >= floor]
        chosen = min(ready or sessions, key=lambda s: s["last"])
        requests.append(Request(
            index=i, tokens=list(chosen["history"]),
            max_new_tokens=outputs[i], due_s=due,
            session_id=chosen["id"], shared_tokens=chosen["shared"],
            extra={"turn": chosen["turn"], "tenant": chosen["tenant"]},
        ))
        chosen["last"] = due
        chosen["turn"] += 1
        grown = len(chosen["history"]) + growth
        if chosen["turn"] >= turns or grown > max_prompt:
            sessions[sessions.index(chosen)] = fresh(sessions.index(chosen))
        else:
            chosen["shared"] = len(chosen["history"])
            chosen["history"] = chosen["history"] + _ids(rng, growth, vocab)
    return requests


# -- shapes to warm -----------------------------------------------------


def warm_requests(traffic: Dict[str, Any], vocab: int) -> List[Request]:
    """The requests set-up sends, in order, so that every program the
    mix's listed shapes need is compiled (or loaded) before the window
    opens. ``shapes.prompt_lens`` are cold prompts of those lengths;
    ``shapes.extend`` lists [prefix, suffix] pairs: a prompt of
    ``prefix`` ids, then the same ids plus ``suffix`` more, which takes
    the prefix cache's rewind-and-extend path. ``shapes.readmit`` lists
    such pairs whose first half is sent FIRST and second half LAST: by
    then the entries in between have pushed the prefix out to the spill
    tier, so the second half takes the readmit path. Seed-independent:
    the same programs every run."""
    rng = _rng(0, "warm")
    shapes = traffic.get("shapes", {})
    max_new = int(shapes.get("warm_new_tokens", 40))
    out: List[Request] = []
    last: List[Request] = []
    for prefix, suffix in shapes.get("readmit", []):
        base = _ids(rng, int(prefix), vocab)
        sid = f"warm-readmit-{len(last)}"
        out.append(Request(index=len(out), tokens=base, max_new_tokens=max_new,
                           session_id=sid))
        last.append(Request(index=0, tokens=base + _ids(rng, int(suffix), vocab),
                            max_new_tokens=max_new, session_id=sid))
    for plen in shapes.get("prompt_lens", []):
        out.append(Request(index=len(out), tokens=_ids(rng, int(plen), vocab),
                           max_new_tokens=max_new))
    for prefix, suffix in shapes.get("extend", []):
        base = _ids(rng, int(prefix), vocab)
        sid = f"warm-{len(out)}"
        out.append(Request(index=len(out), tokens=base, max_new_tokens=max_new,
                           session_id=sid))
        out.append(Request(index=len(out),
                           tokens=base + _ids(rng, int(suffix), vocab),
                           max_new_tokens=max_new, session_id=sid))
    for req in last:
        req.index = len(out)
        out.append(req)
    return out


# -- training tokens ----------------------------------------------------


def train_tokens(traffic: Dict[str, Any], vocab: int, seed: int):
    """The flat token stream of a ``train`` mix: ``windows`` windows
    of ``seq_len + 1`` ids, uniform over the vocabulary, from the seed
    (numpy's PCG64; every row differs)."""
    import numpy as np

    count = int(traffic["windows"]) * (int(traffic["seq_len"]) + 1)
    return np.random.default_rng(seed).integers(
        0, vocab, size=count, dtype=np.int32
    )


def batch_rows(step: int, batch: int, n_windows: int) -> List[int]:
    """Which windows the trainer's loader serves at 0-based ``step``
    (``TokenShardDataset.batch_at`` with seed 0): an affine walk with a
    stride coprime to the window count. Copied arithmetic; the
    reference reads the same rows from the same shards."""
    from math import gcd

    stride = 1
    for cand in (7919, 104729, 1299709, 15485863):
        if n_windows > 1 and gcd(cand % n_windows or 1, n_windows) == 1:
            stride = cand % n_windows or 1
            break
    rows = []
    for j in range(batch):
        epoch, pos = divmod(step * batch + j, n_windows)
        rows.append((stride * pos + epoch * 7919) % n_windows)
    return rows
