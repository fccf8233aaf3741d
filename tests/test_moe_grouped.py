"""The routed experts' grouped kernel (ops/moe_grouped_matmul.py through
models/moe.py ``sparse_experts``), interpreted, against a loop-free
float32 reference: ``sum_e gate_e * SwiGLU_e(h)`` by a dense einsum
over the held experts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models import mla_moe, moe
from containerpilot_tpu.ops import moe_grouped_matmul as grouped

D, F = 128, 256


def _routing(case, n, k, experts, lo, hi, seed):
    rng = np.random.default_rng(seed)
    if case == "none_held_here":
        # every choice falls outside [lo, hi)
        outside = [e for e in range(experts) if not lo <= e < hi]
        idx = np.stack([rng.permutation(outside)[:k] for _ in range(n)])
    else:
        idx = np.stack([rng.permutation(experts)[:k] for _ in range(n)])
    if case == "nobody_chose_one":
        # expert lo + 1 is nobody's: its choosers take a free expert
        for row in idx:
            free = [e for e in range(experts)
                    if e != lo + 1 and e not in row]
            row[row == lo + 1] = free[0]
        assert not (idx == lo + 1).any()
    if case == "one_fills_several_tiles":
        # expert lo is every token's choice
        for row in idx:
            if lo not in row:
                row[0] = lo
    gates = rng.uniform(0.1, 1.0, (n, k)).astype(np.float32)
    return idx.astype(np.int32), gates


def _dense(h, idx, gates, w, lo):
    """Every held expert over every token, masked by the gates: float32
    at HIGHEST, no loop, no sort."""
    held = w[0].shape[0]
    h = h.astype(jnp.float32)
    w_gate, w_up, w_down = (x.astype(jnp.float32) for x in w)
    hp = jax.lax.Precision.HIGHEST
    act = jax.nn.silu(jnp.einsum("nd,edf->enf", h, w_gate, precision=hp)
                      ) * jnp.einsum("nd,edf->enf", h, w_up, precision=hp)
    y = jnp.einsum("enf,efd->end", act, w_down, precision=hp)
    chosen = idx[None] == (lo + jnp.arange(held))[:, None, None]
    weight = jnp.sum(jnp.where(chosen, gates[None], 0.0), axis=-1)
    return jnp.einsum("en,end->nd", weight, y, precision=hp)


#: (case, tokens, k, experts, held range); the first three are the
#: benchmark's cells at toy widths: 2.65, 8.9 and 16 assignments an
#: expert
REGIMES = [
    ("ep-decode", 64, 8, 192, (24, 36)),
    ("ssm-decode", 64, 10, 72, (36, 72)),
    ("block-decode", 64, 8, 32, (0, 32)),
    ("nobody_chose_one", 24, 4, 16, (0, 16)),
    ("one_fills_several_tiles", 200, 4, 16, (4, 12)),
    ("held_mostly_elsewhere", 12, 4, 16, (7, 8)),
    ("none_held_here", 24, 4, 16, (4, 8)),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("f_tiled", [False, True], ids=["f-whole", "f-tiled"])
@pytest.mark.parametrize("case,n,k,experts,held", REGIMES,
                         ids=[r[0] for r in REGIMES])
def test_grouped_kernel_matches_the_dense_reference(
        case, n, k, experts, held, f_tiled, dtype, monkeypatch):
    lo, hi = held
    itemsize = jnp.dtype(dtype).itemsize
    if f_tiled:
        # room for one expert's matrices at half of F, twice
        monkeypatch.setattr(
            grouped, "WEIGHT_BUDGET", 2 * 3 * D * (F // 2) * itemsize)
    assert grouped.f_tile(D, F, itemsize) == (F // 2 if f_tiled else F)
    keys = jax.random.split(jax.random.PRNGKey(n + k), 4)
    w = tuple(
        (jax.random.normal(key, shape) * shape[1] ** -0.5).astype(dtype)
        for key, shape in zip(keys, [
            (hi - lo, D, F), (hi - lo, D, F), (hi - lo, F, D)]))
    h = jax.random.normal(keys[3], (n, D)).astype(dtype)
    idx, gates = _routing(case, n, k, experts, lo, hi, seed=n)
    out, counts = jax.jit(
        lambda h, idx, gates, w: moe.sparse_experts(
            h, idx, gates, *w, lo, experts))(
        h, jnp.asarray(idx), jnp.asarray(gates), w)
    assert out.dtype == jnp.float32 and out.shape == (n, D)
    want = np.asarray(_dense(h, jnp.asarray(idx), jnp.asarray(gates), w, lo))
    scale = max(1.0, float(np.abs(want).max()))
    # bfloat16: the activation is rounded once before the down-projection
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert np.abs(np.asarray(out) - want).max() < tol * scale
    mine = np.array([(idx == e).sum() for e in range(lo, hi)])
    assert list(np.asarray(counts)) == list(mine)
    # the counters the pool keeps: tiles the kernel ran and their rows
    block = moe.expert_block(n, k, experts)
    tiles = int(sum(-(-c // block) for c in mine))
    assert int(moe.expert_tiles(counts, block)) == tiles
    assert tiles <= grouped.tiles_bound(n, k, hi - lo, block)
    stats = mla_moe.named_stats(mla_moe._count(
        jnp.zeros((len(mla_moe.STATS_HEAD) + hi - lo,), jnp.int32),
        n, [counts], block), hi - lo)
    assert stats["expert_tiles"] == tiles
    assert stats["expert_tile_rows"] == tiles * block
    assert stats["assignments_here"] == int(mine.sum())
    assert stats["load"] == list(mine)
    if case == "none_held_here":
        assert tiles == 0 and stats["tile_fill"] is None
        assert not np.asarray(out).any()
    else:
        assert stats["tile_fill"] == mine.sum() / (tiles * block)
    if case == "nobody_chose_one":
        assert mine[1] == 0
    if case == "one_fills_several_tiles":
        assert mine[0] == n > block


@pytest.mark.parametrize("cell,d,f,rows,tile", [
    ("sdar-30b-a3b", 2048, 768, 256, 768),
    ("granite-4-h-small", 4096, 768, 64, 768),
    ("ax-k1", 7168, 2048, 64, 512),
    # a 1,536-token prefill keeps 44 MB of float32 sums beside them
    ("ax-k1 prefill", 7168, 2048, 1536, 256),
])
def test_f_is_tiled_only_where_an_expert_does_not_fit(cell, d, f, rows, tile):
    """The three cells' published widths in bfloat16: SDAR's 9.4 MB and
    granite's 18.9 MB expert fit whole twice over, A.X-K1's 88 MB does
    not."""
    kept = 4 * 128 * d * 4 + rows * d * 4
    assert grouped.f_tile(d, f, 2, kept) == tile
    assert f % tile == 0 and tile % grouped.LANES == 0


def test_a_long_prompt_runs_in_row_chunks(monkeypatch):
    """Tokens whose float32 sums would not fit the fast memory beside
    the weights are cut into row chunks, each a call of its own: the
    same sums, the chunks' counts added."""
    n, k, experts, lo, hi = 40, 4, 16, 0, 16
    monkeypatch.setattr(grouped, "VMEM_BUDGET", 2 * 16 * D * 4)
    assert grouped.rows_bound(D) == 16 < n
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    w = tuple(jax.random.normal(key, shape) * shape[1] ** -0.5
              for key, shape in zip(keys, [
                  (hi - lo, D, F), (hi - lo, D, F), (hi - lo, F, D)]))
    h = jax.random.normal(keys[3], (n, D))
    idx, gates = _routing("random", n, k, experts, lo, hi, seed=5)
    out, counts = moe.sparse_experts(
        h, jnp.asarray(idx), jnp.asarray(gates), *w, lo, experts)
    want = np.asarray(_dense(h, jnp.asarray(idx), jnp.asarray(gates), w, lo))
    assert out.shape == (n, D)
    assert np.abs(np.asarray(out) - want).max() < 2e-5 * np.abs(want).max()
    assert list(np.asarray(counts)) == [
        int((idx == e).sum()) for e in range(lo, hi)]
