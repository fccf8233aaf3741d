"""Order statistics for timings, and the reporting rule that goes with
them: a median and a tail, each with its sample count."""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; None for an empty sample. A missing value (a
    failed request) is passed as ``math.inf`` and sorts last, so it
    misses any limit."""
    if not values:
        return None
    ordered = sorted(values)
    if len(ordered) == 1:
        return ordered[0]
    rank = (len(ordered) - 1) * q / 100.0
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(ordered) - 1)
    if ordered[hi] == math.inf:
        return math.inf if rank > lo else ordered[lo]
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def summary(values: Sequence[float]) -> Dict[str, Optional[float]]:
    """What an earlier output line says of a timing."""
    return {
        "n": len(values),
        "p50": percentile(values, 50),
        "p95": percentile(values, 95),
        "max": max(values) if values else None,
    }


def worst_leaf_gap(program: Dict[str, List[float]],
                   reference: Dict[str, List[float]]) -> Tuple[float, str]:
    """Two sets of norms, one per leaf (and layer): the widest gap
    between the program's norm and the reference's, measured against
    the reference's norm of that leaf or of the median leaf, whichever
    is larger (some leaves' norms are all but zero). A leaf the
    program lacks counts as a norm of 0. Returns the gap and the
    leaf's name."""
    flat = sorted(v for values in reference.values() for v in values)
    median = flat[len(flat) // 2]
    worst, where = 0.0, ""
    for name, values in reference.items():
        theirs = program.get(name, [])
        for layer, ref in enumerate(values):
            got = theirs[layer] if layer < len(theirs) else 0.0
            gap = abs(got - ref) / max(ref, median, 1e-30)
            if math.isnan(gap):  # the widest gap there is: no limit holds
                return gap, f"{name}[{layer}]"
            if gap > worst:
                worst, where = gap, f"{name}[{layer}]"
    return worst, where


def leaf_sum_gap(program: Dict[str, List[float]], reference: Dict[str, List[float]],
                 norms: Dict[str, List[float]]) -> float:
    """Two sets of plain sums, one per leaf (and layer): the gaps
    between them, each measured against the reference's NORM of that
    leaf or of the median leaf, whichever is larger (a sum of signed
    elements may itself be all but zero), as their root mean square
    over the leaves. Rounding noise that a norm hides in its square
    shows here in the first order; the mean over leaves is steadier
    from seed to seed than the worst of them."""
    flat = sorted(v for values in norms.values() for v in values)
    median = flat[len(flat) // 2]
    squares = []
    for name, values in reference.items():
        theirs = program.get(name, [])
        for layer, ref in enumerate(values):
            got = theirs[layer] if layer < len(theirs) else 0.0
            squares.append(((got - ref) / max(norms[name][layer], median, 1e-30)) ** 2)
    return math.sqrt(sum(squares) / len(squares))
