"""The arithmetic ``correct`` rests on for a training run: the gap
between two sets of per-leaf norms by the worst leaf."""
import math

import pytest

from benchmark.harness.stats import worst_leaf_gap

REFERENCE = {"embed": [4.0], "layers/wq": [2.0, 2.0], "layers/norm": [1e-9, 1e-9],
             "unembed": [8.0]}


@pytest.mark.parametrize("program,gap,leaf", [
    # the same norms: no gap
    (REFERENCE, 0.0, ""),
    # one layer of one leaf 10 % off, against that leaf's own norm
    ({**REFERENCE, "layers/wq": [2.0, 2.2]}, 0.1, "layers/wq[1]"),
    # an all-but-zero leaf is measured against the MEDIAN leaf (2.0), so
    # its rounding noise cannot be the worst gap
    ({**REFERENCE, "layers/norm": [3e-9, 1e-9]}, 1e-9, "layers/norm[0]"),
    # a step that returns its state unchanged: every change norm is 0
    ({name: [0.0] * len(v) for name, v in REFERENCE.items()}, 1.0, "embed[0]"),
    # a leaf the program does not report counts as 0
    ({k: v for k, v in REFERENCE.items() if k != "unembed"}, 1.0, "unembed[0]"),
])
def test_worst_leaf_gap(program, gap, leaf):
    got, where = worst_leaf_gap(program, REFERENCE)
    assert got == pytest.approx(gap) and where == leaf


def test_worst_leaf_gap_nan_is_the_widest():
    got, where = worst_leaf_gap({**REFERENCE, "embed": [math.nan]}, REFERENCE)
    assert math.isnan(got) and where == "embed[0]"
    assert not got <= 1e9  # so no limit holds


def test_launcher_and_reference_read_the_same_part_of_a_leaf():
    """The parameters' change is read on a part of each large leaf:
    the launcher's observer (launch/common.py) and the reference
    (configs/mistral_reference.py) each state the rule, and have to
    agree on it, leaf for leaf."""
    import os

    import numpy as np

    from benchmark.harness.spec import load_module

    here = os.path.dirname(os.path.abspath(__file__))
    common = load_module(os.path.join(here, "..", "launch", "common.py"))
    reference = load_module(os.path.join(here, "..", "configs", "mistral_reference.py"))
    rng = np.random.default_rng(5)
    flat = {  # the reference's names; two leaves above 2**20 elements
        "embed": rng.normal(size=(4096, 512)), "wq": rng.normal(size=(2, 2048, 4, 128)),
        "norm_attn": rng.normal(size=(2, 512)), "norm_out": rng.normal(size=(512,)),
    }
    flat = {k: v.astype(np.float32) for k, v in flat.items()}
    theirs = reference._norms({k: np.asarray(v) for k, v in flat.items()}, sampled=True)
    program = {"embed": flat["embed"], "norm_out": flat["norm_out"],
               "layers": {"wq": flat["wq"], "norm_attn": flat["norm_attn"]}}
    import jax.numpy as jnp

    ours = {}
    for name, (part, stacked) in common._sampled(
            {k: (jnp.asarray(v) if k != "layers" else
                 {n: jnp.asarray(a) for n, a in v.items()})
             for k, v in program.items()}).items():
        rows = part.reshape(part.shape[0], -1) if stacked else part.reshape(1, -1)
        ours[name] = [float(np.sqrt(np.sum(np.square(r, dtype=np.float64)))) for r in rows]
    assert ours.keys() == theirs.keys()
    assert worst_leaf_gap(ours, theirs)[0] < 1e-6
    # and it IS a part: the whole of wq has 16 times the sampled rows
    whole = float(np.sqrt(np.sum(np.square(flat["wq"][0], dtype=np.float64))))
    assert theirs["layers/wq"][0] < 0.3 * whole


def test_leaf_sum_gap():
    from benchmark.harness.stats import leaf_sum_gap

    norms = {"a": [10.0, 10.0], "b": [1e-9]}
    sums = {"a": [3.0, -2.0], "b": [0.0]}
    assert leaf_sum_gap(sums, sums, norms) == 0.0
    # one layer 0.5 off against its leaf's norm of 10: 0.05 there, 0 on the
    # other two, so the root mean square is 0.05 / sqrt(3)
    off = {"a": [3.5, -2.0], "b": [0.0]}
    assert leaf_sum_gap(off, sums, norms) == pytest.approx(0.05 / math.sqrt(3))
    # an all-but-zero leaf is measured against the median norm
    assert leaf_sum_gap({"a": [3.0, -2.0], "b": [1.0]}, sums, norms) \
        == pytest.approx(0.1 / math.sqrt(3))
