"""ops/ragged_decode.py on the CPU (interpret mode) at toy widths,
against the plain contraction it stands in for
(models/decoder_hybrid.py ``_pair_attention`` over the same leaves and
a mask by position): rows that end at ragged positions, and nothing
read past the key block that holds a row's own position."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models import decoder_hybrid as dh
from containerpilot_tpu.ops import ragged_decode as rd

BLOCK, LENGTH = 8, 32
CFG = dh.DecoderHybridConfig()
#: a row's position by case; the other rows of the call stand elsewhere
POSITIONS = {
    "the first position": 0,
    "one short of a block's edge": BLOCK - 2,
    "a block's last position": BLOCK - 1,
    "a block's first position": BLOCK,
    "the leaf's last position": LENGTH - 1,
    "a dead slot past the leaf's end": LENGTH + 9,
}
#: float32 rounding of an online softmax against one over the whole row
#: (read: 4e-7 of values within 2), the decode tests' REL of the largest
TOLERANCE = 2e-5


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(rd, "BLOCK_LEN", BLOCK)


def _leaves(dtype, rows):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    pairs, width = CFG.kv_pairs, CFG.pair_dim
    maps = 2 * CFG.n_heads // CFG.n_kv_heads
    draw = lambda key, shape: jax.random.normal(
        key, shape, jnp.float32).astype(dtype)
    return (draw(keys[0], (rows, 1, pairs, maps, width)),
            draw(keys[1], (rows, pairs, LENGTH, width)),
            draw(keys[2], (rows, pairs, LENGTH, width)))


def _plain(q, k, v, at):
    valid = (jnp.arange(LENGTH)[None, :] <= at[:, None])[:, None, :]
    return dh._pair_attention(q, k, v, valid, CFG)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(POSITIONS))
def test_a_ragged_row_reads_what_the_plain_contraction_reads(case, dtype):
    """Three rows at different positions, the case's among them: the
    kernel gives ``_pair_attention``'s float32 output for every row.
    What lies past the block that holds a row's position is poisoned
    for the kernel (NaN: a block it fetched or computed on would show)
    and zero for the plain read."""
    at = np.asarray([3 * BLOCK + 1, POSITIONS[case], BLOCK + 3], np.int32)
    q, k, v = _leaves(dtype, len(at))
    covered = (np.minimum(at, LENGTH - 1) // BLOCK + 1) * BLOCK
    dead = jnp.asarray(np.arange(LENGTH)[None, :] >= covered[:, None])
    dead = dead[:, None, :, None]
    want = _plain(q, jnp.where(dead, 0, k), jnp.where(dead, 0, v),
                  jnp.asarray(at))
    got = dh._plane_attention(
        q, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v),
        jnp.asarray(at), CFG)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    gap = float(jnp.abs(got - want).max())
    assert gap < TOLERANCE * float(jnp.abs(want).max()), gap


def test_the_tolerance_fails_weights_rounded_to_bfloat16():
    """The same comparison with the softmax weights rounded to the
    leaf's bfloat16 before the weighted sum (the one-pass product the
    kernel's three parts replace) does not pass: the tolerance sees a
    lowered precision."""
    at = jnp.asarray([LENGTH - 1, 5], jnp.int32)
    q, k, v = _leaves(jnp.bfloat16, 2)
    want = _plain(q, k, v, at)
    valid = (jnp.arange(LENGTH)[None, :] <= at[:, None])[:, None, None, None]

    @jax.jit
    def lowered(q, k, v):
        scores = jnp.einsum(
            "bqpjd,bpkd->bpjqk", q, k,
            preferred_element_type=jnp.float32) * CFG.head_dim ** -0.5
        weights = jax.nn.softmax(jnp.where(valid, scores, dh.NEG_INF), axis=-1)
        rounded = weights.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.einsum("bpjqk,bpkd->bqpjd", rounded, v.astype(jnp.float32),
                          precision=dh.HIGHEST)

    gap = float(jnp.abs(lowered(q, k, v) - want).max())
    assert gap > 10 * TOLERANCE * float(jnp.abs(want).max()), gap


@pytest.mark.parametrize("length, size", [
    (3072, 512), (64, 64), (768, 256), (24, 8), (21, 21)])
def test_a_block_divides_the_leafs_length(monkeypatch, length, size):
    monkeypatch.setattr(rd, "BLOCK_LEN", 512)
    assert rd.block_len(length) == size


def test_the_positions_covered_are_whole_blocks_to_each_rows_own():
    at = jnp.asarray([0, BLOCK - 1, BLOCK, LENGTH - 1, LENGTH + 100],
                     jnp.int32)
    assert int(rd.positions_covered(at, LENGTH)) == (
        BLOCK + BLOCK + 2 * BLOCK + LENGTH + LENGTH)
