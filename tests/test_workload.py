"""TPU-workload tests on the virtual 8-device CPU mesh: model numerics,
pallas kernel parity, sharded train step, graft entry points."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
)
from containerpilot_tpu.ops.attention import (
    causal_attention,
    flash_attention_forward,
)
from containerpilot_tpu.parallel import (
    MeshPlan,
    init_train_state,
    make_mesh,
    make_train_step,
)


CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
    max_seq_len=64,
)


def test_forward_shapes_and_finiteness():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab_size, jnp.int32
    )
    logits = jax.jit(lambda p, t: forward(p, t, CFG))(params, tokens)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_loss_decreases_under_training():
    """Overfit a single tiny batch: loss must drop substantially."""
    mesh = make_mesh(jax.devices()[:1], plan=MeshPlan(1, 1))
    state = init_train_state(jax.random.PRNGKey(0), CFG, mesh,
                             learning_rate=1e-2)
    step = make_train_step(CFG, mesh, learning_rate=1e-2)
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (2, 33), 0, CFG.vocab_size, jnp.int32
    )
    first = None
    for _ in range(10):
        state, loss = step(state, tokens)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.8, (first, float(loss))


def test_causality():
    """Changing future tokens must not change past logits."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 128, jnp.int32)
    t2 = t1.at[0, 10:].set((t1[0, 10:] + 1) % 128)
    l1 = forward(params, t1, CFG)
    l2 = forward(params, t2, CFG)
    np.testing.assert_allclose(
        np.asarray(l1[0, :10]), np.asarray(l2[0, :10]), rtol=1e-4, atol=1e-4
    )


def test_flash_attention_matches_xla():
    """The pallas kernel (interpret mode on CPU) must match the einsum
    reference."""
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (2, 256, 2, 64)  # [batch, seq, heads, head_dim]
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ref = causal_attention(q, k, v)
    flash = flash_attention_forward(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(flash), rtol=2e-3, atol=2e-3
    )


def test_flash_attention_rejects_ragged_seq():
    q = jnp.zeros((1, 100, 2, 64))
    with pytest.raises(ValueError, match="not a multiple"):
        flash_attention_forward(q, q, q)


def test_flash_attention_grad_parity():
    """The pallas backward kernels (dq, dk/dv) must match jax.grad
    through the einsum reference."""
    from containerpilot_tpu.ops.flash import flash_attention

    rng = jax.random.PRNGKey(3)
    kq, kk, kv, kc = jax.random.split(rng, 4)
    shape = (2, 256, 2, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    cot = jax.random.normal(kc, shape, jnp.float32)

    with jax.default_matmul_precision("float32"):
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(causal_attention(q, k, v) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_fl = jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, 64, 64) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
    for ref, fl in zip(g_ref, g_fl):
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(fl), rtol=2e-3, atol=2e-3
        )


def test_flash_attention_mismatched_block_sizes():
    """block_q != block_k exercises the rows-fully-masked-in-this-block
    paths of the online softmax and both backward kernels."""
    from containerpilot_tpu.ops.flash import flash_attention

    rng = jax.random.PRNGKey(4)
    kq, kk, kv, kc = jax.random.split(rng, 4)
    shape = (1, 256, 2, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    cot = jax.random.normal(kc, shape, jnp.float32)
    with jax.default_matmul_precision("float32"):
        ref = causal_attention(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(causal_attention(q, k, v) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
        for bq, bk in [(128, 64), (64, 128)]:
            out = flash_attention(q, k, v, bq, bk)
            np.testing.assert_allclose(
                np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3
            )
            g_fl = jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, bq, bk) * cot
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            for r, f in zip(g_ref, g_fl):
                np.testing.assert_allclose(
                    np.asarray(r), np.asarray(f), rtol=2e-3, atol=2e-3
                )


def test_flash_auto_select_threshold():
    """TransformerConfig auto-picks flash at/after flash_min_seq."""
    from containerpilot_tpu.models.transformer import flash_eligible

    cfg = TransformerConfig(flash_min_seq=1024)
    assert not flash_eligible(cfg, 512)
    assert flash_eligible(cfg, 1024)
    assert flash_eligible(cfg, 4096)
    assert not flash_eligible(cfg, 1100)  # not 128-aligned
    assert not flash_eligible(TransformerConfig(flash_min_seq=0), 4096)


def test_training_through_auto_flash_matches_causal():
    """A train step whose seq length crosses flash_min_seq runs the
    pallas fwd+bwd kernels; the loss must match the einsum path."""
    cfg_flash = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=1, d_ff=128,
        max_seq_len=128, flash_min_seq=128,
    )
    cfg_causal = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=1, d_ff=128,
        max_seq_len=128, flash_min_seq=0,
    )
    params = init_params(jax.random.PRNGKey(0), cfg_flash)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 129), 0, 128, jnp.int32
    )
    with jax.default_matmul_precision("float32"):
        l_flash, g_flash = jax.value_and_grad(loss_fn)(
            params, tokens, cfg_flash
        )
        l_causal, g_causal = jax.value_and_grad(loss_fn)(
            params, tokens, cfg_causal
        )
    np.testing.assert_allclose(
        float(l_flash), float(l_causal), rtol=1e-2
    )
    flat_f = jax.tree_util.tree_leaves(g_flash)
    flat_c = jax.tree_util.tree_leaves(g_causal)
    for f, c in zip(flat_f, flat_c):
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(c), rtol=5e-2, atol=5e-3
        )


def test_sharded_train_step_flash_shard_map():
    """dp x tp training where the seq length triggers the shard_map
    flash path (pallas under manual partitioning)."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=1, d_ff=128,
        max_seq_len=128, flash_min_seq=128,
    )
    mesh = make_mesh(jax.devices()[:4], plan=MeshPlan(2, 2))
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (4, 129), 0, 128, jnp.int32
    )
    state, loss = step(state, tokens)
    assert bool(jnp.isfinite(loss))


def test_mesh_factorization():
    mesh = make_mesh(jax.devices()[:8])
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (2, 4)
    mesh1 = make_mesh(jax.devices()[:1])
    assert mesh1.devices.shape == (1, 1)
    with pytest.raises(ValueError):
        make_mesh(jax.devices()[:8], plan=MeshPlan(3, 2))


def test_sharded_train_step_8_devices():
    """The full tp x dp train step over the virtual 8-device mesh."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64,
    )  # heads/ff/vocab divisible by the 4-way model axis
    mesh = make_mesh(jax.devices()[:8])
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )
    state, loss = step(state, tokens)
    assert bool(jnp.isfinite(loss))
    assert int(state.step) == 1
    # params actually sharded: wq's model axis split over 4 devices
    wq_sharding = state.params["layers"]["wq"].sharding
    assert len(wq_sharding.device_set) == 8


def test_lr_schedule_shapes():
    """Warmup ramps from 0, cosine decays to the floor, constant stays
    a plain float (state layout unchanged for existing checkpoints)."""
    from containerpilot_tpu.parallel import make_optimizer
    from containerpilot_tpu.parallel.train import lr_schedule

    assert lr_schedule(3e-4) == 3e-4
    warm = lr_schedule(1e-3, warmup_steps=10)
    assert float(warm(0)) == 0.0
    np.testing.assert_allclose(float(warm(5)), 5e-4, rtol=1e-6)
    np.testing.assert_allclose(float(warm(10)), 1e-3, rtol=1e-6)
    np.testing.assert_allclose(float(warm(1000)), 1e-3, rtol=1e-6)
    full = lr_schedule(1e-3, warmup_steps=10, decay_steps=90)
    np.testing.assert_allclose(float(full(10)), 1e-3, rtol=1e-6)
    # halfway through decay: midpoint of peak and floor
    np.testing.assert_allclose(float(full(55)), 5.5e-4, rtol=1e-3)
    np.testing.assert_allclose(float(full(100)), 1e-4, rtol=1e-3)
    np.testing.assert_allclose(float(full(500)), 1e-4, rtol=1e-3)
    # a scheduled optimizer still initializes and updates
    opt = make_optimizer(1e-3, warmup_steps=2, decay_steps=4)
    params = {"w": jnp.ones((4,))}
    opt_state = opt.init(params)
    updates, _ = opt.update(
        {"w": jnp.full((4,), 0.5)}, opt_state, params
    )
    assert updates["w"].shape == (4,)


def test_grad_accumulation_matches_full_batch():
    """accum_steps=2 must produce the same loss and parameter update as
    the single-shot step on the same batch (equal-size chunks: mean of
    chunk means == full-batch mean)."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:8])
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size, jnp.int32
    )
    state_a = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    state_b = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step_full = make_train_step(cfg, mesh)
    step_accum = make_train_step(cfg, mesh, accum_steps=2)
    state_a, loss_a = step_full(state_a, tokens)
    state_b, loss_b = step_accum(state_b, tokens)
    np.testing.assert_allclose(
        float(loss_a), float(loss_b), rtol=1e-5, atol=1e-6
    )
    flat_a = jax.tree_util.tree_leaves(state_a.params)
    flat_b = jax.tree_util.tree_leaves(state_b.params)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )
    with pytest.raises(ValueError, match="not divisible"):
        step3 = make_train_step(cfg, mesh, accum_steps=3)
        step3(init_train_state(jax.random.PRNGKey(0), cfg, mesh), tokens)


def test_zero1_shards_moments_and_matches_plain_step():
    """ZeRO-1: adam mu/nu shard over the data axis (per-device moment
    memory drops by the dp factor) and the update stays numerically
    equivalent to the replicated-optimizer step."""
    from containerpilot_tpu.parallel.train import train_state_shardings

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:8])  # data=2, model=4
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )

    plain = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    z1 = init_train_state(jax.random.PRNGKey(0), cfg, mesh, zero1=True)

    # the moments really are sharded over data: wq's mu gains a "data"
    # axis, and each device holds half of it
    mu_plain = plain.opt_state[1][0].mu["layers"]["wq"]
    mu_z1 = z1.opt_state[1][0].mu["layers"]["wq"]
    assert "data" in mu_z1.sharding.spec
    assert "data" not in (mu_plain.sharding.spec or ())
    shard_elems = lambda a: a.addressable_shards[0].data.size
    assert shard_elems(mu_z1) * 2 == shard_elems(mu_plain)

    # the canonical shardings agree with what init produced (pinned
    # in_shardings would otherwise reshard silently)
    shardings = train_state_shardings(cfg, mesh, zero1=True)
    assert shardings.opt_state[1][0].mu["layers"]["wq"] == mu_z1.sharding

    step_plain = make_train_step(cfg, mesh)
    step_z1 = make_train_step(cfg, mesh, zero1=True)
    plain, loss_a = step_plain(plain, tokens)
    z1, loss_b = step_z1(z1, tokens)
    np.testing.assert_allclose(
        float(loss_a), float(loss_b), rtol=1e-6, atol=1e-7
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params),
        jax.tree_util.tree_leaves(z1.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_ring_attention_matches_single_device():
    """Context-parallel ring attention over a 4-way seq axis must match
    single-device causal attention exactly in structure and closely in
    numerics."""
    from containerpilot_tpu.ops import ring_attention
    from containerpilot_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(jax.devices()[:8], plan=MeshPlan(data=2, model=1, seq=4))
    assert mesh.axis_names == ("data", "seq", "model")
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (2, 128, 2, 32)  # [batch, seq, heads, head_dim]
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ref = causal_attention(q, k, v)
    ring = ring_attention(q, k, v, mesh)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(ring), rtol=2e-4, atol=2e-4
    )


def test_cp_generate_matches_unsharded(run):
    """Context-parallel serving prefill: a long prompt sharded over
    an 8-way seq axis rings through prefill, the cache gathers once,
    and the decode produces the same tokens the unsharded path does —
    greedy and with the sampling knobs riding along."""
    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.parallel import (
        MeshPlan,
        cp_generate,
        make_mesh,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
        n_layers=2, d_ff=64, max_seq_len=128, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(
        jax.devices()[:8], plan=MeshPlan(data=1, model=1, seq=8)
    )
    prompt = jax.random.randint(
        jax.random.PRNGKey(3), (1, 64), 0, cfg.vocab_size, jnp.int32
    )

    plain = generate(params, prompt, cfg, 8, 128)
    cp = cp_generate(params, prompt, cfg, mesh, 8, 128)
    assert [int(t) for t in cp[0]] == [int(t) for t in plain[0]]

    # the sampling contract rides unchanged (seeded + logit_bias)
    rng = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(5), 0)])
    kw = dict(temperature=0.9, top_k=12, rng=rng,
              logit_bias={7: -100.0})
    plain_s = generate(params, prompt, cfg, 8, 128, **kw)
    cp_s = cp_generate(params, prompt, cfg, mesh, 8, 128, **kw)
    assert [int(t) for t in cp_s[0]] == [int(t) for t in plain_s[0]]
    assert 7 not in [int(t) for t in cp_s[0]]

    # a non-axis-divisible prompt: the divisible head rings, the
    # remainder extends the gathered cache — still byte-equal
    odd = jax.random.randint(
        jax.random.PRNGKey(9), (1, 30), 0, cfg.vocab_size, jnp.int32
    )
    plain_odd = generate(params, odd, cfg, 6, 128)
    cp_odd = cp_generate(params, odd, cfg, mesh, 6, 128)
    assert [int(t) for t in cp_odd[0]] == [int(t) for t in plain_odd[0]]

    # int8 KV cache composes: the ring reads the dequant roundtrip in
    # prefill and the gathered cache carries the scales
    import dataclasses as _dc

    cfg_q = _dc.replace(cfg, kv_int8=True)
    plain_q = generate(params, prompt, cfg_q, 6, 128)
    cp_q = cp_generate(params, prompt, cfg_q, mesh, 6, 128)
    assert [int(t) for t in cp_q[0]] == [int(t) for t in plain_q[0]]

    # cp x tp: model-sharded params on a (seq, model) mesh — the ring
    # keeps heads on 'model' inside its shard_map, the gathered cache
    # decodes tensor-parallel, output still byte-equal
    from containerpilot_tpu.parallel import shard_params

    mesh_tp = make_mesh(
        jax.devices()[:8], plan=MeshPlan(data=1, model=2, seq=4)
    )
    sharded = shard_params(params, mesh_tp, cfg)
    cp_tp = cp_generate(sharded, prompt, cfg, mesh_tp, 8, 128)
    assert [int(t) for t in cp_tp[0]] == [int(t) for t in plain[0]]

    # contract checks fail loudly
    with pytest.raises(ValueError, match="shorter than"):
        cp_generate(params, jnp.ones((1, 6), jnp.int32), cfg, mesh,
                    4, 128)
    with pytest.raises(ValueError, match="exceeds max_len"):
        cp_generate(params, prompt, cfg, mesh, 128, 128)
    no_seq = make_mesh(jax.devices()[:8], plan=MeshPlan(data=1, model=8))
    with pytest.raises(ValueError, match="no 'seq' axis"):
        cp_generate(params, prompt, cfg, no_seq, 4, 128)


def test_cp_remainder_extend_steps_are_capped(monkeypatch):
    """The bucketed-head remainder must extend in pieces no larger
    than max(axis, prefill_chunk): a pod bucket can leave a remainder
    just under head tokens, and an uncapped power-of-two step would
    run one chunk-x-cache attention far above the ring's per-device
    activation bound — the worst case --sp advertises protection
    against (ADVICE r5). Host-only: the ring head and the extend
    program are stubbed so just the decomposition runs, and the piece
    set stays the finite {2^k <= cap} + tails that keeps the pod's
    compile-skew story intact."""
    import containerpilot_tpu.models.decode as dec
    from containerpilot_tpu.parallel import MeshPlan, make_mesh
    from containerpilot_tpu.parallel import context as ctx

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=128, dtype=jnp.float32,
    )
    mesh = make_mesh(
        jax.devices()[:2], plan=MeshPlan(data=1, model=1, seq=2)
    )
    monkeypatch.setattr(
        ctx, "_cp_prefill_fn",
        lambda *a: lambda params, sharded: ("logits", {}),
    )
    widths = []

    def fake_extend(_cfg):
        def ext(params, cache, chunk):
            widths.append(int(chunk.shape[1]))
            return "logits", cache

        return ext

    monkeypatch.setattr(dec, "_jitted_extend", fake_extend)
    prompt = np.zeros((1, 39), np.int32)
    # head 8 leaves a 31-token remainder — the uncapped decomposition
    # would run a single 16-wide piece even with --prefill-chunk 8
    for prefill_chunk, cap in ((8, 8), (0, 2)):
        widths.clear()
        ctx.cp_prefill_with_remainder(
            None, prompt, cfg, mesh, 128, head=8,
            prefill_chunk=prefill_chunk,
        )
        assert sum(widths) == 39 - 8, widths
        assert max(widths) <= cap, widths


@pytest.mark.parametrize(
    "plan_kw", [dict(model=1, seq=8), dict(model=2, seq=4)],
    ids=["cp8", "cp4xtp2"],
)
def test_serve_cp_long_prompt_matches_vanilla(run, plan_kw):
    """--cp end-to-end: a server with a seq-axis mesh (pure, or
    composed with tensor parallelism — model-sharded params on a
    seq x model mesh) answers long prompts byte-identically to a
    vanilla server, short prompts take the normal path, and
    /v1/model reports the cp config; bad compositions fail at
    construction."""
    import json
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.parallel import (
        MeshPlan,
        make_mesh,
        shard_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
        n_layers=2, d_ff=64, max_seq_len=128, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(
        jax.devices()[:8], plan=MeshPlan(data=1, **plan_kw)
    )
    srv_params = (
        shard_params(params, mesh, cfg)
        if plan_kw["model"] > 1 else params
    )
    cp_srv = InferenceServer(
        cfg, srv_params, "127.0.0.1", 0, max_len=128, cp_mesh=mesh,
        cp_min_len=32,
    )
    vanilla = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=128)

    # --cp composes with --slots: the engine rings long-prompt
    # admissions over the seq axis (the pod's --sp recipe), so a
    # slot-pooled server answers long prompts identically too
    slot_cp_srv = InferenceServer(
        cfg, srv_params, "127.0.0.1", 0, max_len=128, cp_mesh=mesh,
        cp_min_len=32, slots=2,
    )
    # an explicit threshold no admissible prompt can reach fails at
    # startup; the DERIVED default instead self-clamps below max_len
    with pytest.raises(ValueError, match="never engages"):
        InferenceServer(
            cfg, params, "127.0.0.1", 0, max_len=128, cp_mesh=mesh,
            cp_min_len=128,
        )
    defaulted = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=32, cp_mesh=mesh,
    )
    assert defaulted.cp_min_len == 31  # min(8*8, max_len-1)

    import numpy as _np

    long_prompt = _np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=45
    ).tolist()

    def fetch(port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read().decode())

    async def scenario():
        import asyncio

        await cp_srv.run()
        await vanilla.run()
        await slot_cp_srv.run()
        loop = asyncio.get_event_loop()

        def go():
            reqs = [
                {"tokens": [long_prompt], "max_new_tokens": 6},
                {"tokens": [long_prompt], "max_new_tokens": 5,
                 "temperature": 0.8, "top_k": 10, "seed": 4},
                {"tokens": [[1, 2, 3]], "max_new_tokens": 4},  # short
            ]
            pairs = [
                (fetch(cp_srv.port, r), fetch(vanilla.port, r),
                 fetch(slot_cp_srv.port, r))
                for r in reqs
            ]
            info = urllib.request.urlopen(
                f"http://127.0.0.1:{cp_srv.port}/v1/model", timeout=30
            ).read().decode()
            return pairs, json.loads(info)

        out = await loop.run_in_executor(None, go)
        await cp_srv.stop()
        await vanilla.stop()
        await slot_cp_srv.stop()
        return out

    pairs, info = run(scenario(), timeout=300)
    for got, want, slot_got in pairs:
        assert got["tokens"] == want["tokens"]
        # the slot-pooled cp server answers identically (engine
        # admissions ring the same maximal head cp_generate uses)
        assert slot_got["tokens"] == want["tokens"]
    assert info["cp"] == {"seq": plan_kw["seq"], "min_len": 32}


def test_ring_attention_gqa_native():
    """The ring rotates unrepeated (grouped) kv heads and must match
    repeat_kv + single-device attention."""
    from containerpilot_tpu.models.transformer import repeat_kv as rep
    from containerpilot_tpu.ops import ring_attention
    from containerpilot_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(jax.devices()[:8], plan=MeshPlan(data=2, model=1, seq=4))
    rng = jax.random.PRNGKey(6)
    kq, kk, kv = jax.random.split(rng, 3)
    b, s, h, kvh, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(kq, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(kk, (b, s, kvh, hd), jnp.float32)
    v = jax.random.normal(kv, (b, s, kvh, hd), jnp.float32)
    with jax.default_matmul_precision("float32"):
        ref = causal_attention(q, rep(k, h), rep(v, h))
        ring = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh)
        )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(ring), rtol=2e-4, atol=2e-4
    )
    with pytest.raises(ValueError, match="divide"):
        ring_attention(q, k[:, :, :0], v[:, :, :0], mesh)


def test_ring_attention_mqa_fallback_on_tp_axis():
    """MQA (1 kv head) with a >1 tp axis: grouped heads can't shard
    over model, so the ring falls back to rotating full heads — and
    must still be exact."""
    from containerpilot_tpu.models.transformer import repeat_kv as rep
    from containerpilot_tpu.ops import ring_attention
    from containerpilot_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(jax.devices()[:8], plan=MeshPlan(data=2, model=2, seq=2))
    rng = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(rng, 3)
    b, s, h, kvh, hd = 2, 64, 4, 1, 32
    q = jax.random.normal(kq, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(kk, (b, s, kvh, hd), jnp.float32)
    v = jax.random.normal(kv, (b, s, kvh, hd), jnp.float32)
    with jax.default_matmul_precision("float32"):
        ref = causal_attention(q, rep(k, h), rep(v, h))
        ring = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh)
        )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(ring), rtol=2e-4, atol=2e-4
    )


def test_gqa_context_parallel_train_step():
    """dp x sp x tp with a GQA model: the ring gets the unrepeated kv
    (gqa_native contract) and the loss matches the 2D-mesh step."""
    from containerpilot_tpu.parallel import context_parallel_config

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=128, max_seq_len=64,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(8), (4, 65), 0, cfg.vocab_size, jnp.int32
    )
    mesh2 = make_mesh(jax.devices()[:8], plan=MeshPlan(data=4, model=2))
    state2 = init_train_state(jax.random.PRNGKey(0), cfg, mesh2)
    _, loss2 = make_train_step(cfg, mesh2)(state2, tokens)
    mesh3 = make_mesh(
        jax.devices()[:8], plan=MeshPlan(data=2, seq=2, model=2)
    )
    cfg3 = context_parallel_config(cfg, mesh3)
    assert getattr(cfg3.attention_fn, "gqa_native", False)
    state3 = init_train_state(jax.random.PRNGKey(0), cfg3, mesh3)
    _, loss3 = make_train_step(cfg3, mesh3)(state3, tokens)
    assert bool(jnp.isfinite(loss3))
    np.testing.assert_allclose(float(loss2), float(loss3), rtol=5e-3)


def test_ring_attention_validates_inputs():
    from containerpilot_tpu.ops import ring_attention
    from containerpilot_tpu.parallel import MeshPlan, make_mesh

    mesh2d = make_mesh(jax.devices()[:8])  # no seq axis
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="no 'seq' axis"):
        ring_attention(q, q, q, mesh2d)
    mesh3d = make_mesh(jax.devices()[:8], plan=MeshPlan(2, 1, 4))
    q_ragged = jnp.zeros((1, 66, 2, 16))
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q_ragged, q_ragged, q_ragged, mesh3d)


def test_context_parallel_train_step():
    """Full dp x sp x tp train step with ring attention inside the
    model: loss must match the XLA-attention step closely."""
    from containerpilot_tpu.parallel import context_parallel_config

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(7), (4, 65), 0, cfg.vocab_size, jnp.int32
    )
    # reference: plain 2D mesh step
    mesh2 = make_mesh(jax.devices()[:8], plan=MeshPlan(data=4, model=2))
    state2 = init_train_state(jax.random.PRNGKey(0), cfg, mesh2)
    _, loss2 = make_train_step(cfg, mesh2)(state2, tokens)
    # context-parallel: 3D mesh, ring attention in the forward
    mesh3 = make_mesh(
        jax.devices()[:8], plan=MeshPlan(data=2, seq=2, model=2)
    )
    cfg3 = context_parallel_config(cfg, mesh3)
    state3 = init_train_state(jax.random.PRNGKey(0), cfg3, mesh3)
    _, loss3 = make_train_step(cfg3, mesh3)(state3, tokens)
    assert bool(jnp.isfinite(loss3))
    np.testing.assert_allclose(
        float(loss2), float(loss3), rtol=5e-3
    )


def test_restore_params_from_scheduled_checkpoint(tmp_path):
    """A checkpoint written under an lr-scheduled optimizer (extra
    count state in the opt tree) must still open with the serving
    path's default skeleton: the opt_state placeholder structure comes
    from the checkpoint's own metadata, not the caller."""
    from containerpilot_tpu.parallel import (
        abstract_train_state,
        make_optimizer,
        restore_params,
        save_checkpoint,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:1])
    opt = make_optimizer(1e-3, warmup_steps=2, decay_steps=10)
    state = init_train_state(
        jax.random.PRNGKey(0), cfg, mesh, optimizer=opt
    )
    step = make_train_step(cfg, mesh, optimizer=opt)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size, jnp.int32
    )
    state, _ = step(state, tokens)
    save_checkpoint(str(tmp_path), 1, state)

    # the serving process knows nothing of the training schedule
    abstract = abstract_train_state(jax.random.PRNGKey(0), cfg, mesh)
    params, restored_step = restore_params(str(tmp_path), abstract)
    assert int(restored_step) == 1
    np.testing.assert_allclose(
        np.asarray(params["embed"]),
        np.asarray(state.params["embed"]),
        rtol=1e-6,
    )


def test_checkpoint_save_restore_roundtrip(tmp_path):
    """Crash-resume: save a sharded TrainState, restore into a fresh
    one, training state carries over."""
    from containerpilot_tpu.parallel import (
        latest_step,
        restore_checkpoint,
        save_checkpoint,
    )

    mesh = make_mesh(jax.devices()[:8])
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64,
    )
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )
    state, _ = step(state, tokens)
    state, _ = step(state, tokens)
    ckdir = str(tmp_path / "ckpts")
    save_checkpoint(ckdir, 2, state)
    assert latest_step(ckdir) == 2

    fresh = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    restored = restore_checkpoint(ckdir, fresh)
    assert restored is not None
    assert int(restored.step) == 2
    np.testing.assert_allclose(
        np.asarray(state.params["norm_out"]),
        np.asarray(restored.params["norm_out"]),
    )
    # restored state is usable: one more step runs
    restored, loss = step(restored, tokens)
    assert bool(jnp.isfinite(loss))
    assert restore_checkpoint(str(tmp_path / "nope"), fresh) is None
    # pruning keeps only the newest `keep` checkpoints
    save_checkpoint(ckdir, 3, restored, keep=1)
    assert latest_step(ckdir) == 3
    import os

    assert sorted(os.listdir(ckdir)) == ["step_3"]


def test_restored_params_pickles_and_deepcopies():
    """RestoredParams crosses process boundaries (serving restores in
    executors); tuple.__getnewargs__ must supply all three ctor args."""
    import copy
    import pickle

    from containerpilot_tpu.parallel.checkpoint import RestoredParams

    r = RestoredParams({"w": 1}, 5, True)
    params, step = r  # stays a 2-tuple for existing unpack sites
    assert (params, step) == ({"w": 1}, 5)
    for clone in (pickle.loads(pickle.dumps(r)), copy.deepcopy(r)):
        assert tuple(clone) == tuple(r) and clone.ema is True


def test_restore_params_only(tmp_path):
    """Serving restore: params (and step) come back; optimizer moments
    stay orbax PLACEHOLDERs and are never materialized."""
    from containerpilot_tpu.parallel import (
        abstract_train_state,
        restore_params,
        save_checkpoint,
    )

    mesh = make_mesh(jax.devices()[:8])
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64,
    )
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )
    state, _ = step(state, tokens)
    ckdir = str(tmp_path / "ckpts")
    save_checkpoint(ckdir, 1, state)

    abstract = abstract_train_state(jax.random.PRNGKey(0), cfg, mesh)
    params, ck_step = restore_params(ckdir, abstract)
    assert int(ck_step) == 1
    np.testing.assert_allclose(
        np.asarray(state.params["norm_out"]), np.asarray(params["norm_out"])
    )
    # the restored params serve a forward directly
    logits = forward(params, tokens[:, :8], cfg)
    assert bool(jnp.isfinite(logits).all())
    assert restore_params(str(tmp_path / "nope"), abstract) is None


def test_prefill_through_flash_matches_forward():
    """A flash-eligible prompt length routes prefill through the pallas
    kernels; last-position logits must equal the full forward."""
    from containerpilot_tpu.models.decode import prefill

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=256, dtype=jnp.float32, flash_min_seq=128,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab_size, jnp.int32
    )
    with jax.default_matmul_precision("float32"):
        ref = forward(params, tokens, cfg)[:, -1, :]
        logits, cache = prefill(params, tokens, cfg, max_len=256)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(logits), rtol=2e-3, atol=2e-3
    )
    assert int(cache["pos"]) == 128


def test_flash_forward_gqa_native():
    """flash_attention_forward reads unrepeated kv heads (GQA) and
    must match the repeat_kv + einsum reference."""
    rng = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(rng, 3)
    b, s, h, kvh, hd = 2, 256, 4, 2, 64
    q = jax.random.normal(kq, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(kk, (b, s, kvh, hd), jnp.float32)
    v = jax.random.normal(kv, (b, s, kvh, hd), jnp.float32)
    from containerpilot_tpu.models.transformer import repeat_kv as rep

    with jax.default_matmul_precision("float32"):
        ref = causal_attention(q, rep(k, h), rep(v, h))
        out = flash_attention_forward(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3
    )
    with pytest.raises(ValueError, match="dividing"):
        # 3 kv heads don't divide 4 query heads
        kk3 = jnp.concatenate([k, k[:, :, :1]], axis=2)
        flash_attention_forward(q, kk3, kk3, 64, 64)
    with pytest.raises(ValueError, match="incompatible"):
        # cache-shaped kv longer than the prompt must be rejected, not
        # silently truncated
        k2 = jnp.concatenate([k, k], axis=1)
        flash_attention_forward(q, k2, k2, 64, 64)
    with pytest.raises(ValueError, match="incompatible"):
        flash_attention_forward(q, k[:1], v[:1], 64, 64)  # batch mismatch
    with pytest.raises(ValueError, match="incompatible"):
        flash_attention_forward(q, k[:, :, :0], v[:, :, :0], 64, 64)

    # the differentiable path must refuse unrepeated GQA kv — its
    # backward would return wrong-shaped dk/dv
    from containerpilot_tpu.ops.flash import flash_attention

    with pytest.raises(ValueError, match="full-head"):
        flash_attention(q, k, v, 64, 64)


def test_gqa_prefill_through_flash_matches_forward():
    """A GQA model's flash-eligible prefill (unrepeated kv through the
    kernel) must match the full forward."""
    from containerpilot_tpu.models.decode import prefill

    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, n_layers=1,
        d_ff=64, max_seq_len=256, dtype=jnp.float32, flash_min_seq=128,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab_size, jnp.int32
    )
    with jax.default_matmul_precision("float32"):
        ref = forward(params, tokens, cfg)[:, -1, :]
        logits, _cache = prefill(params, tokens, cfg, max_len=256)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(logits), rtol=2e-3, atol=2e-3
    )


def test_incremental_decode_matches_full_forward():
    """Prefill + decode_step logits must equal the full forward's
    per-position logits (teacher forcing)."""
    from containerpilot_tpu.models.decode import decode_step, prefill

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq_len=32, dtype=jnp.float32,  # f32 for tight comparison
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size, jnp.int32
    )
    full = forward(params, tokens, cfg)  # [b, 12, vocab]

    # prefill on the first 6, then feed the rest one at a time
    logits, cache = prefill(params, tokens[:, :6], cfg, max_len=16)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, 5]), rtol=2e-4, atol=2e-4
    )
    for i in range(6, 12):
        logits, cache = decode_step(params, cache, tokens[:, i], cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, i]), rtol=2e-4, atol=2e-4,
            err_msg=f"position {i}",
        )


def test_generate_greedy_deterministic():
    from containerpilot_tpu.models.decode import generate

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (1, 4), 0, 64, jnp.int32
    )
    out1 = generate(params, prompt, cfg, max_new_tokens=8, max_len=16)
    out2 = generate(params, prompt, cfg, max_new_tokens=8, max_len=16)
    assert out1.shape == (1, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(out1.min()) >= 0 and int(out1.max()) < 64


def test_sample_logits_top_k_top_p():
    """top-k keeps the k best ids; top-p keeps the minimal nucleus."""
    from containerpilot_tpu.models.decode import sample_logits

    # id 3 is the mode (p ~ 0.64 at temp 1), then 2, 1, 0, 4
    logits = jnp.tile(
        jnp.asarray([[0.0, 1.0, 2.0, 3.0, -1.0]], jnp.float32), (512, 1)
    )
    one = jnp.float32(1.0)
    key = jax.random.PRNGKey(7)
    top2 = sample_logits(logits, key, one, top_k=2)
    assert set(np.asarray(top2).tolist()) <= {2, 3}
    # top_k=1 is greedy regardless of the key
    top1 = sample_logits(logits, jax.random.PRNGKey(8), one, top_k=1)
    assert set(np.asarray(top1).tolist()) == {3}
    # nucleus 0.5: the mode alone already covers the mass
    nucleus = sample_logits(logits, key, one, top_p=0.5)
    assert set(np.asarray(nucleus).tolist()) == {3}
    # nucleus 0.9 needs {3, 2, 1}; id 0 and 4 stay excluded
    wide = sample_logits(logits, key, one, top_p=0.9)
    assert set(np.asarray(wide).tolist()) <= {1, 2, 3}
    # unfiltered sampling can reach every id
    free = sample_logits(logits, key, jnp.float32(3.0))
    assert len(set(np.asarray(free).tolist())) >= 4


def test_generate_sampling_modes_and_eos():
    from containerpilot_tpu.models.decode import generate

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (2, 4), 0, 64, jnp.int32
    )
    greedy = generate(params, prompt, cfg, max_new_tokens=6, max_len=16)
    # temperature ~0 with top_k=1 reproduces greedy
    t1 = generate(
        params, prompt, cfg, max_new_tokens=6, max_len=16,
        temperature=0.5, top_k=1,
    )
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(t1))
    # sampled output stays in-vocab
    sampled = generate(
        params, prompt, cfg, max_new_tokens=6, max_len=16,
        temperature=1.0, top_k=8, top_p=0.9,
    )
    assert int(sampled.min()) >= 0 and int(sampled.max()) < 64

    # eos early-stop: make the first greedy token the eos — the rest of
    # the row must be pad
    eos = int(greedy[0, 0])
    stopped = generate(
        params, prompt, cfg, max_new_tokens=6, max_len=16,
        eos_id=eos, pad_id=63,
    )
    row = np.asarray(stopped[0]).tolist()
    first_eos = row.index(eos)
    assert all(t == 63 for t in row[first_eos + 1:])

    with pytest.raises(ValueError, match="top_k"):
        generate(params, prompt, cfg, max_new_tokens=2, max_len=16,
                 top_p=1.5)


def test_decode_chunk_matches_decode_steps():
    """The multi-token incremental step (speculative verify) must be
    numerically equivalent to sequential single-token steps."""
    from containerpilot_tpu.models.decode import (
        decode_chunk, decode_step, prefill,
    )

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size, jnp.int32
    )
    _logits, cache_a = prefill(params, tokens[:, :6], cfg, max_len=16)
    _logits, cache_b = prefill(params, tokens[:, :6], cfg, max_len=16)
    chunk_logits, cache_a = decode_chunk(params, cache_a, tokens[:, 6:12], cfg)
    for i in range(6):
        step_logits, cache_b = decode_step(params, cache_b, tokens[:, 6 + i], cfg)
        np.testing.assert_allclose(
            np.asarray(chunk_logits[:, i]), np.asarray(step_logits),
            rtol=2e-4, atol=2e-4, err_msg=f"chunk position {i}",
        )
    assert int(cache_a["pos"]) == int(cache_b["pos"]) == 12
    np.testing.assert_allclose(
        np.asarray(cache_a["k"]), np.asarray(cache_b["k"]),
        rtol=1e-5, atol=1e-5,
    )


def test_speculative_matches_vanilla_greedy():
    """Speculative decoding must reproduce the target's greedy output
    EXACTLY for any draft — the draft changes speed, never content."""
    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.speculative import (
        layer_prefix_draft, speculative_generate,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=3, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (1, 5), 0, 64, jnp.int32
    )
    want = generate(params, prompt, cfg, max_new_tokens=20, max_len=40)

    # weak draft: 1-layer prefix
    dparams, dcfg = layer_prefix_draft(params, cfg, 1)
    got, stats = speculative_generate(
        params, dparams, prompt, cfg, dcfg,
        max_new_tokens=20, max_len=40, speculate=4,
    )
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert stats["tokens"] == 20 and stats["rounds"] >= 5

    # perfect draft (the target itself): every round fully accepts
    got2, stats2 = speculative_generate(
        params, params, prompt, cfg, cfg,
        max_new_tokens=20, max_len=40, speculate=4,
    )
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got2))
    # token 1 comes from prefill; a perfect draft fully accepts every
    # round, emitting k+1 = 5 per round (4 drafts + the bonus token):
    # 19 remaining tokens take ceil(19/5) = 4 verify rounds
    assert stats2["rounds"] == 4
    assert stats2["accepted_drafts"] == 16

    with pytest.raises(ValueError, match="batch 1"):
        speculative_generate(
            params, dparams, jnp.ones((2, 3), jnp.int32), cfg, dcfg,
            max_new_tokens=4, max_len=40,
        )
    with pytest.raises(ValueError, match="draft layers"):
        layer_prefix_draft(params, cfg, 3)

    # eos early-exit: pick the greedy row's 3rd token as "eos" — the
    # spec loop must stop paying rounds once a round emits it, and the
    # prefix through that token must still match vanilla greedy exactly
    want_row = np.asarray(want)[0].tolist()
    eos = want_row[2]
    cut = want_row.index(eos) + 1  # first occurrence may be earlier
    got3, stats3 = speculative_generate(
        params, dparams, prompt, cfg, dcfg,
        max_new_tokens=20, max_len=40, speculate=4, eos_id=eos,
    )
    row3 = np.asarray(got3)[0].tolist()
    assert eos in row3 and row3.index(eos) == cut - 1
    assert row3[:cut] == want_row[:cut]
    assert stats3["tokens"] < 20  # stopped early, not padded to max
    assert stats3["rounds"] < stats["rounds"]


def test_inference_server_end_to_end(run):
    """The serving path: warmup -> health -> generate over HTTP."""
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,  # tight score-parity check
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"} if body else {},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, resp.read().decode()
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()  # includes warmup
        loop = asyncio.get_event_loop()
        health = await loop.run_in_executor(None, fetch, "/health")
        gen = await loop.run_in_executor(
            None,
            lambda: fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 5},
            ),
        )
        bad = await loop.run_in_executor(
            None,
            lambda: fetch(
                "/v1/generate",
                {"tokens": [[999]], "max_new_tokens": 5},
            ),
        )
        score = await loop.run_in_executor(
            None,
            lambda: fetch("/v1/score", {"tokens": [[1, 2, 3, 4]]}),
        )
        bad_score = await loop.run_in_executor(
            None,
            lambda: fetch("/v1/score", {"tokens": [[7]]}),
        )
        await server.stop()
        return health, gen, bad, score, bad_score

    import json
    import urllib.error

    health, gen, bad, score, bad_score = run(scenario(), timeout=120)
    assert health[0] == 200
    assert gen[0] == 200
    out = json.loads(gen[1])["tokens"]
    assert len(out) == 1 and len(out[0]) == 5
    assert bad[0] == 422 and "token ids" in bad[1]

    # teacher-forced scoring: one logprob per continuation token, all
    # negative, matching the forward's log-softmax
    assert score[0] == 200
    scored = json.loads(score[1])
    assert len(scored["logprobs"][0]) == 3
    assert all(lp < 0 for lp in scored["logprobs"][0])
    from containerpilot_tpu.models.transformer import forward as _fwd

    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    logp = jax.nn.log_softmax(_fwd(params, toks[:, :-1], cfg), axis=-1)
    expect = [float(logp[0, i, int(toks[0, i + 1])]) for i in range(3)]
    np.testing.assert_allclose(
        scored["logprobs"][0], expect, rtol=1e-3, atol=1e-3
    )
    np.testing.assert_allclose(
        scored["sums"][0], sum(expect), rtol=1e-3, atol=1e-3
    )
    assert bad_score[0] == 422 and ">= 2 ids" in bad_score[1]


def test_generate_per_row_params_and_key_independence():
    """Per-row sampling knobs and keys: a greedy row batched next to a
    sampled row matches its solo greedy output, and a sampled row's
    output is independent of what it's batched with."""
    from containerpilot_tpu.models.decode import generate

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    rows = jax.random.randint(
        jax.random.PRNGKey(1), (2, 4), 0, 64, jnp.int32
    )
    solo_greedy = generate(params, rows[:1], cfg, 8, 16)
    key_b = jax.random.PRNGKey(7)
    solo_sampled = generate(
        params, rows[1:], cfg, 8, 16, temperature=1.0, top_k=8,
        rng=key_b[None, :],
    )
    mixed = generate(
        params, rows, cfg, 8, 16,
        temperature=[0.0, 1.0], top_k=[0, 8],
        rng=jnp.stack([jax.random.PRNGKey(0), key_b]),
    )
    np.testing.assert_array_equal(
        np.asarray(solo_greedy[0]), np.asarray(mixed[0])
    )
    np.testing.assert_array_equal(
        np.asarray(solo_sampled[0]), np.asarray(mixed[1])
    )
    with pytest.raises(ValueError, match="scalar or \\[batch\\]"):
        generate(params, rows, cfg, 8, 16, temperature=[0.5, 0.5, 0.5])


def test_inference_server_batches_concurrent_requests(run):
    """Concurrent clients share the slot pool — their decode rounds
    are the same device dispatches — with unchanged per-request
    results."""
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=32, slot_chunk=4,
    )

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read())

    bodies = [
        {"tokens": [[1, 2, 3]], "max_new_tokens": 24,
         "temperature": 1.0, "top_k": 8, "seed": i}
        for i in range(6)
    ] + [{"tokens": [[1, 2, 3]], "max_new_tokens": 24}]  # one greedy

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()
        engine = server.slot_engine

        def mark():
            return dict(engine.stats, admissions=engine.phases.admissions)

        marks = [mark()]
        # sequential baseline (one request at a time)
        sequential = []
        for body in bodies:
            sequential.append(
                await loop.run_in_executor(None, fetch, body)
            )
        marks.append(mark())
        concurrent = await asyncio.gather(*[
            loop.run_in_executor(None, fetch, body) for body in bodies
        ])
        marks.append(mark())
        await server.stop()
        return sequential, concurrent, marks

    import json

    sequential, concurrent, marks = run(scenario(), timeout=300)
    # identical results regardless of what shared the pool (per-row
    # keys from each request's seed)
    assert sequential == list(concurrent)

    def spent(key, phase):
        return marks[phase + 1][key] - marks[phase][key]

    # both phases admitted every request once and emitted every token
    for phase in (0, 1):
        assert spent("admissions", phase) == len(bodies)
        assert spent("tokens_out", phase) == 24 * len(bodies)
    # and the 7 concurrent requests rode fewer device dispatches:
    # 7 sequences over 4 slots decode side by side
    assert spent("dispatches", 1) < spent("dispatches", 0), marks


def test_inference_server_speculative(run):
    """Two servers, same weights, one speculative: identical greedy
    output over HTTP; sampled and batched requests fall back."""
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=3, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    vanilla = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=64)
    spec = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=64,
        draft_layers=1, speculate=4,
    )
    with pytest.raises(ValueError, match="speculate"):
        InferenceServer(cfg, params, "127.0.0.1", 0, max_len=64,
                        draft_layers=1, speculate=0)

    def fetch(port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read())

    async def scenario():
        import asyncio

        await vanilla.run()
        await spec.run()
        warm_tokens = spec.slot_engine.tokens_out  # warmup's dummy
        loop = asyncio.get_event_loop()
        greedy_body = {"tokens": [[3, 1, 4, 1, 5]], "max_new_tokens": 24}
        a = await loop.run_in_executor(
            None, lambda: fetch(vanilla.port, greedy_body)
        )
        b = await loop.run_in_executor(
            None, lambda: fetch(spec.port, greedy_body)
        )
        # eos trim must agree between the padded and speculative paths
        eos = a["tokens"][0][2]
        eos_body = {**greedy_body, "eos_id": eos}
        ae = await loop.run_in_executor(
            None, lambda: fetch(vanilla.port, eos_body)
        )
        be = await loop.run_in_executor(
            None, lambda: fetch(spec.port, eos_body)
        )
        sampled = await loop.run_in_executor(
            None, lambda: fetch(spec.port, {
                "tokens": [[3, 1, 4]], "max_new_tokens": 8,
                "temperature": 1.0, "seed": 7,
            })
        )
        batched = await loop.run_in_executor(
            None, lambda: fetch(spec.port, {
                "tokens": [[1, 2], [3, 4]], "max_new_tokens": 4,
            })
        )

        def model_info():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{spec.port}/v1/model", timeout=5
            ) as resp:
                return json.loads(resp.read())

        info = await loop.run_in_executor(None, model_info)
        await vanilla.stop()
        await spec.stop()
        return a, b, ae, be, sampled, batched, info, warm_tokens

    import json

    a, b, ae, be, sampled, batched, info, warm_tokens = run(
        scenario(), timeout=300
    )
    assert a == b
    assert ae == be
    assert len(sampled["tokens"][0]) == 8
    assert len(batched["tokens"]) == 2 and len(batched["tokens"][0]) == 4
    # observability: /v1/model reports the speculative + batching
    # setup, including the step-program engine the greedy requests
    # rode (draft+verify = 2 device dispatches per round)
    spec_info = dict(info["speculative"])
    engine_stats = spec_info.pop("engine")
    assert spec_info == {"draft_layers": 1, "speculate": 4}
    assert engine_stats["slots"] == 1
    assert engine_stats["dispatches"] >= 2
    # the sampled and the two-row request fell back to the slot
    # engine, one sequence a row: 8 + 2 x 4 tokens; the greedy ones
    # never touched it
    assert info["batching"] == {"max_batch_rows": 16}
    assert info["slot_engine"]["tokens_out"] - warm_tokens == 16
    assert info["stream"] is True


def test_lora_zero_init_and_training(tmp_path):
    """A fresh adapter reproduces the base exactly (B = 0); training
    it lowers the loss with the base frozen; the adapter checkpoints
    round-trip, including the params-only restore serving uses."""
    from containerpilot_tpu.models.lora import apply_lora, init_lora_params
    from containerpilot_tpu.parallel import (
        make_lora_train_step,
        restore_checkpoint,
        restore_params,
        save_checkpoint,
    )
    from containerpilot_tpu.parallel.sharding import shard_params

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:8])
    base = shard_params(
        init_params(jax.random.PRNGKey(0), cfg), mesh, cfg
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size, jnp.int32
    )

    # exact zero-delta at init
    lora = init_lora_params(jax.random.PRNGKey(2), cfg, rank=4)
    merged = apply_lora(base, lora, cfg)
    np.testing.assert_array_equal(
        np.asarray(forward(base, tokens[:, :-1], cfg)),
        np.asarray(forward(merged, tokens[:, :-1], cfg)),
    )

    init_fn, step_fn, abstract = make_lora_train_step(
        cfg, mesh, rank=4, learning_rate=1e-2
    )
    state = init_fn(jax.random.PRNGKey(3))
    base_before = jax.tree_util.tree_map(np.asarray, base)
    losses = []
    for _ in range(15):
        state, loss = step_fn(state, base, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1, losses
    # the base never moved; the adapter did
    for a, b in zip(
        jax.tree_util.tree_leaves(base_before),
        jax.tree_util.tree_leaves(base),
    ):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert float(jnp.abs(state.params["wq_b"]).max()) > 0

    # resume + serving restore
    save_checkpoint(str(tmp_path), 15, state)
    resumed = restore_checkpoint(str(tmp_path), abstract)
    assert int(resumed.step) == 15
    lora_only, step_n = restore_params(str(tmp_path), abstract)
    assert int(step_n) == 15
    np.testing.assert_array_equal(
        np.asarray(lora_only["wq_a"]), np.asarray(state.params["wq_a"])
    )

    with pytest.raises(ValueError, match="rank"):
        init_lora_params(jax.random.PRNGKey(0), cfg, rank=0)


def test_distributed_initialize_from_catalog_single_process(tmp_path):
    """The catalog rendezvous path: process 0 registers the coordinator
    and initializes; (multi-process needs multiple hosts, so we drive
    the registration + discovery logic plus a real 1-process init)."""
    from containerpilot_tpu.discovery import FileCatalogBackend
    from containerpilot_tpu.parallel.distributed import (
        COORDINATOR_SERVICE,
        _discover_coordinator,
    )

    backend = FileCatalogBackend(str(tmp_path))
    # a "process 0" on another host registered already:
    from containerpilot_tpu.discovery import ServiceRegistration

    backend.service_register(
        ServiceRegistration(
            id="jax-coordinator-host0", name=COORDINATOR_SERVICE,
            port=8476, address="10.0.0.1", ttl=600,
        ),
        status="passing",
    )
    addr = _discover_coordinator(backend, 8476, timeout=5, poll_interval=0.1)
    assert addr == "10.0.0.1:8476"
    with pytest.raises(TimeoutError):
        _discover_coordinator(
            FileCatalogBackend(str(tmp_path / "empty")), 8476,
            timeout=0.3, poll_interval=0.1,
        )


_RENDEZVOUS_WORKER = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, n, catalog, coord_port = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], int(sys.argv[4])
)
from containerpilot_tpu.discovery.consul import ConsulBackend
from containerpilot_tpu.parallel.distributed import initialize_from_catalog

backend = ConsulBackend(address=catalog)
initialize_from_catalog(
    backend, pid, n, coordinator_port=coord_port,
    advertise_address="127.0.0.1", timeout=90, poll_interval=0.2,
)
assert jax.process_count() == n, jax.process_count()
import jax.numpy as jnp

total = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
    jnp.ones((jax.local_device_count(),), jnp.float32)
)
print("PSUM", float(total[0]), flush=True)
"""


def test_distributed_two_process_catalog_rendezvous(tmp_path):
    """TWO real OS processes rendezvous through a live catalog server
    and complete a cross-process psum (reference scenario:
    integration_tests/tests/test_discovery_consul — two containers
    finding each other through the catalog)."""
    import socket as socketlib
    import subprocess
    import sys
    import time as timelib
    import urllib.request

    import os

    def free_port():
        with socketlib.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    catalog_port, coord_port = free_port(), free_port()
    worker = tmp_path / "worker.py"
    worker.write_text(_RENDEZVOUS_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    env.pop("XLA_FLAGS", None)  # 1 CPU device per process

    server = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=repo, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        deadline = timelib.monotonic() + 30
        while True:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{catalog_port}"
                    "/v1/health/service/none",
                    timeout=1,
                )
                break
            except Exception:
                if timelib.monotonic() > deadline:
                    raise TimeoutError("catalog server never came up")
                timelib.sleep(0.2)

        procs = [
            subprocess.Popen(
                [sys.executable, str(worker), str(pid), "2",
                 f"127.0.0.1:{catalog_port}", str(coord_port)],
                cwd=repo, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for pid in (0, 1)
        ]
        outs = [p.communicate(timeout=180) for p in procs]
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
            assert "PSUM 2.0" in out, (out, err[-500:])
    finally:
        server.terminate()
        server.wait(timeout=10)


def test_memory_efficient_attention_value_and_grad():
    """Flash-algorithm training attention: forward and ALL THREE input
    gradients must match the einsum reference."""
    from containerpilot_tpu.ops.flash_training import (
        memory_efficient_attention,
    )

    rng = jax.random.PRNGKey(0)
    kq, kk, kv, kd = jax.random.split(rng, 4)
    shape = (2, 256, 2, 32)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    cotangent = jax.random.normal(kd, shape, jnp.float32)

    ref_out = causal_attention(q, k, v)
    out = memory_efficient_attention(q, k, v, 64)
    np.testing.assert_allclose(
        np.asarray(ref_out), np.asarray(out), rtol=2e-4, atol=2e-4
    )

    def ref_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v) * cotangent)

    def mea_loss(q, k, v):
        return jnp.sum(memory_efficient_attention(q, k, v, 64) * cotangent)

    ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    mea_grads = jax.grad(mea_loss, argnums=(0, 1, 2))(q, k, v)
    for name, rg, mg in zip("qkv", ref_grads, mea_grads):
        np.testing.assert_allclose(
            np.asarray(rg), np.asarray(mg), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name}",
        )


def test_memory_efficient_attention_in_model_training():
    """The model trains with memory-efficient attention bound in."""
    import dataclasses

    from containerpilot_tpu.ops.flash_training import (
        memory_efficient_attention,
    )

    cfg = dataclasses.replace(
        TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
            max_seq_len=64, dtype=jnp.float32,
        ),
        attention_fn=lambda q, k, v: memory_efficient_attention(q, k, v, 32),
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 65), 0, 64, jnp.int32
    )
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    assert bool(jnp.isfinite(loss))
    flat, _ = jax.tree_util.tree_flatten(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat)


def test_abstract_restore_skips_materialization(tmp_path):
    """Resume via the abstract (eval_shape) target: identical result to
    restoring into a materialized state, with correct shardings."""
    from containerpilot_tpu.parallel import (
        restore_checkpoint,
        save_checkpoint,
    )
    from containerpilot_tpu.parallel.train import abstract_train_state

    mesh = make_mesh(jax.devices()[:8])
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64,
    )
    rng = jax.random.PRNGKey(0)
    state = init_train_state(rng, cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )
    state, _ = step(state, tokens)
    ckdir = str(tmp_path / "ck")
    save_checkpoint(ckdir, 1, state)

    abstract = abstract_train_state(rng, cfg, mesh)
    restored = restore_checkpoint(ckdir, abstract)
    assert restored is not None
    assert int(restored.step) == 1
    # shardings landed where the train step expects: step still runs
    wq = restored.params["layers"]["wq"]
    assert wq.sharding.spec == state.params["layers"]["wq"].sharding.spec
    restored, loss = step(restored, tokens)
    assert bool(jnp.isfinite(loss))


def test_gqa_forward_and_decode_parity():
    """Grouped-query attention: 4 query heads over 2 kv heads — the KV
    cache shrinks and incremental decode still matches the forward."""
    from containerpilot_tpu.models.decode import decode_step, init_cache, prefill

    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert params["layers"]["wk"].shape == (2, 64, 2, 16)  # kv heads
    assert params["layers"]["wq"].shape == (2, 64, 4, 16)  # full heads
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, 10), 0, cfg.vocab_size, jnp.int32
    )
    full = forward(params, tokens, cfg)
    assert bool(jnp.isfinite(full).all())

    cache = init_cache(cfg, 1, 16)
    assert cache["k"].shape == (2, 1, 16, 2, 16)  # halved kv-head cache

    logits, cache = prefill(params, tokens[:, :5], cfg, max_len=16)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, 4]), rtol=2e-4, atol=2e-4
    )
    for i in range(5, 10):
        logits, cache = decode_step(params, cache, tokens[:, i], cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, i]), rtol=2e-4,
            atol=2e-4, err_msg=f"position {i}",
        )


def test_gqa_trains_sharded():
    """GQA + tp: kv heads (2) shard over a 2-way model axis."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=128, max_seq_len=64,
    )
    mesh = make_mesh(jax.devices()[:8], plan=MeshPlan(data=4, model=2))
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )
    state, loss = step(state, tokens)
    assert bool(jnp.isfinite(loss))


def test_gqa_default_mesh_replicates_small_kv_axis():
    """GQA with kv_heads smaller than the auto-picked model axis must
    place (replicate wk/wv) instead of crashing."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=128, max_seq_len=64,
    )
    mesh = make_mesh(jax.devices()[:8])  # auto plan: model=4 > kv=2
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )
    state, loss = step(state, tokens)
    assert bool(jnp.isfinite(loss))
    from jax.sharding import PartitionSpec as P

    assert state.params["layers"]["wk"].sharding.spec == P(
        None, None, None, None
    )


def test_int8_quantized_matmul():
    """Weight-only int8: quantization error bounded, pallas kernel
    (interpret mode) matches the XLA dequant path."""
    from containerpilot_tpu.ops import (
        int8_matmul,
        int8_matmul_pallas,
        quantize_int8,
    )

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (128, 256), jnp.float32)
    w = jax.random.normal(kw, (256, 384), jnp.float32)
    w_q, scales = quantize_int8(w)
    assert w_q.dtype == jnp.int8 and scales.shape == (384,)
    # dequantized weights approximate the originals per-channel
    w_hat = w_q.astype(jnp.float32) * scales[None, :]
    assert float(jnp.max(jnp.abs(w_hat - w))) < float(jnp.max(scales)) * 0.51

    exact = x @ w
    ref = int8_matmul(x, w_q, scales)
    # int8 matmul error grows with sqrt(K); relative tolerance
    rel = float(jnp.max(jnp.abs(ref - exact)) / jnp.max(jnp.abs(exact)))
    assert rel < 0.02, rel
    out = int8_matmul_pallas(x, w_q, scales)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), rtol=2e-3, atol=2e-3
    )
    with pytest.raises(ValueError, match="not divisible"):
        int8_matmul_pallas(x[:100], w_q, scales)
    with pytest.raises(ValueError, match="inner dims"):
        int8_matmul_pallas(x[:, :128], w_q, scales)


def test_int8_model_quantization_end_to_end():
    """Model-level weight-only int8: ~4x smaller params, small logit
    error, and the quantized decode path matches the quantized forward
    (teacher forcing) so serving is self-consistent."""
    from containerpilot_tpu.models.decode import decode_step, generate, prefill
    from containerpilot_tpu.models.quantized import (
        is_quantized,
        param_bytes,
        quantize_model_params,
    )

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=128, max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    pq = quantize_model_params(params)
    assert is_quantized(pq) and not is_quantized(params)
    assert param_bytes(params) / param_bytes(pq) > 3.0

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab_size, jnp.int32
    )
    full = forward(params, tokens, cfg)
    quant = forward(pq, tokens, cfg)
    rel = float(jnp.max(jnp.abs(full - quant)) / jnp.max(jnp.abs(full)))
    assert rel < 0.05, rel

    # quantized incremental decode == quantized forward, per position
    logits, cache = prefill(pq, tokens[:, :5], cfg, max_len=16)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(quant[:, 4]), rtol=2e-4, atol=2e-4
    )
    for i in range(5, 10):
        logits, cache = decode_step(pq, cache, tokens[:, i], cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(quant[:, i]), rtol=2e-4,
            atol=2e-4, err_msg=f"position {i}",
        )
    out = generate(pq, tokens[:, :4], cfg, max_new_tokens=4, max_len=16)
    assert out.shape == (2, 4)


def test_int8_fused_decode_matches_dense_dequant():
    """On a tile-aligned model the decode step routes its projections
    through the fused int8 pallas GEMM; logits must match the
    dense-dequant path (same math, different streaming)."""
    from containerpilot_tpu.models import decode as decode_mod
    from containerpilot_tpu.models.decode import decode_step, prefill
    from containerpilot_tpu.models.quantized import (
        can_fuse_int8,
        quantize_model_params,
    )

    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_heads=1, n_layers=2, d_ff=128,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    pq = quantize_model_params(params)
    assert can_fuse_int8(pq["layers"], cfg, rows=2)
    # tiny dims or MoE fall back to dense dequant
    assert not can_fuse_int8(pq["layers"], cfg, rows=10_000)
    small = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=32, dtype=jnp.float32,
    )
    small_q = quantize_model_params(init_params(jax.random.PRNGKey(0), small))
    assert not can_fuse_int8(small_q["layers"], small, rows=2)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size, jnp.int32
    )
    with jax.default_matmul_precision("float32"):
        quant_fwd = forward(pq, tokens, cfg)
        logits, cache = prefill(pq, tokens[:, :4], cfg, max_len=16)
        for i in range(4, 8):
            logits, cache = decode_step(pq, cache, tokens[:, i], cfg)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(quant_fwd[:, i]),
                rtol=2e-3, atol=2e-3, err_msg=f"position {i}",
            )


def test_fsdp_shards_params_and_matches_plain_step():
    """FSDP (ZeRO-3): params AND adam moments shard over the data axis
    (per-device model state drops by the dp factor) while the update
    stays numerically equivalent to the replicated-params step."""
    from containerpilot_tpu.parallel import fsdp_sharding_rules
    from containerpilot_tpu.parallel.train import train_state_shardings

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:8])  # data=2, model=4
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )

    rules = fsdp_sharding_rules(cfg, mesh)
    # every large param gains a data axis; the scan/layer axis never
    # takes it (slicing a scan operand across devices would force a
    # per-iteration gather)
    assert "data" in rules["embed"]
    for name, spec in rules["layers"].items():
        assert spec[0] is None, (name, spec)
        assert "data" in spec, (name, spec)

    plain = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    fs = init_train_state(jax.random.PRNGKey(0), cfg, mesh, rules=rules)

    # params and moments really are sharded over data: each device
    # holds 1/8 of wq (2-way data x 4-way model) vs 1/4 replicated
    shard_elems = lambda a: a.addressable_shards[0].data.size
    wq_p, wq_f = plain.params["layers"]["wq"], fs.params["layers"]["wq"]
    assert shard_elems(wq_f) * 2 == shard_elems(wq_p)
    mu_f = fs.opt_state[1][0].mu["layers"]["wq"]
    assert "data" in mu_f.sharding.spec
    assert shard_elems(mu_f) == shard_elems(wq_f)

    # the canonical shardings agree with what init produced, and
    # zero1=True composes (the moments keep the fsdp placement rather
    # than double-consuming the data axis)
    shardings = train_state_shardings(cfg, mesh, rules=rules, zero1=True)
    assert shardings.opt_state[1][0].mu["layers"]["wq"] == mu_f.sharding

    step_plain = make_train_step(cfg, mesh)
    step_fsdp = make_train_step(cfg, mesh, fsdp=True)
    plain, loss_a = step_plain(plain, tokens)
    fs, loss_b = step_fsdp(fs, tokens)
    np.testing.assert_allclose(
        float(loss_a), float(loss_b), rtol=1e-6, atol=1e-7
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.params),
        jax.tree_util.tree_leaves(fs.params),
    ):
        # reduce-scattered grads reassociate float sums across devices
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


def test_ema_tracks_params_and_checkpoints(tmp_path):
    """with_ema keeps a decay-weighted shadow of the params inside the
    optimizer state: exact vs a hand-rolled recurrence, resolvable by
    the sharding rules, and carried through a checkpoint roundtrip."""
    from containerpilot_tpu.parallel import (
        ema_params,
        make_optimizer,
        restore_checkpoint,
        save_checkpoint,
        with_ema,
    )
    from containerpilot_tpu.parallel import abstract_train_state

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:8])
    decay = 0.9
    opt = with_ema(make_optimizer(1e-2), decay)
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh, optimizer=opt)
    step = make_train_step(cfg, mesh, optimizer=opt)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )

    # ema starts as a copy of the init params
    init_wq = np.asarray(state.params["layers"]["wq"])
    np.testing.assert_array_equal(
        np.asarray(ema_params(state)["layers"]["wq"]), init_wq
    )

    # two steps: ema == d*(d*p0 + (1-d)*p1) + (1-d)*p2
    manual = init_wq
    for _ in range(2):
        state, _ = step(state, tokens)
        manual = decay * manual + (1 - decay) * np.asarray(
            state.params["layers"]["wq"]
        )
    got = np.asarray(ema_params(state)["layers"]["wq"])
    np.testing.assert_allclose(got, manual, rtol=1e-5, atol=1e-7)

    # the ema leaf inherits the param sharding (it mirrors the tree)
    ema_wq = ema_params(state)["layers"]["wq"]
    assert ema_wq.sharding.spec == state.params["layers"]["wq"].sharding.spec

    # checkpoint roundtrip preserves the shadow
    save_checkpoint(str(tmp_path), int(state.step), state)
    abstract = abstract_train_state(
        jax.random.PRNGKey(0), cfg, mesh, optimizer=opt
    )
    restored = restore_checkpoint(str(tmp_path), abstract)
    np.testing.assert_allclose(
        np.asarray(ema_params(restored)["layers"]["wq"]), got,
        rtol=0, atol=0,
    )

    # params-only restore can surface the EMA shadow (what serving
    # --use-ema does): same shape/sharding as params, moments on disk
    from containerpilot_tpu.parallel import restore_params

    got_params, got_step = restore_params(str(tmp_path), abstract)
    ema_restored = restore_params(
        str(tmp_path), abstract, prefer_ema=True
    )
    got_ema, ema_step = ema_restored
    # .ema reports what the restore ACTUALLY returned (evaluate's
    # "ema" report field comes from here, not a metadata re-probe)
    assert ema_restored.ema is True
    assert restore_params(str(tmp_path), abstract).ema is False
    assert int(got_step) == int(ema_step) == int(state.step)
    np.testing.assert_allclose(
        np.asarray(got_ema["layers"]["wq"]), got, rtol=0, atol=0
    )
    # the ema shadow differs from the raw params after training
    assert not np.allclose(
        np.asarray(got_ema["layers"]["wq"]),
        np.asarray(got_params["layers"]["wq"]),
    )

    # prefer_ema on an EMA-less checkpoint falls back to raw params
    plain = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    plain_step = make_train_step(cfg, mesh)
    plain, _ = plain_step(plain, tokens)
    save_checkpoint(str(tmp_path / "plain"), 1, plain)
    plain_abstract = abstract_train_state(jax.random.PRNGKey(0), cfg, mesh)
    fallback_restored = restore_params(
        str(tmp_path / "plain"), plain_abstract, prefer_ema=True
    )
    fallback, _ = fallback_restored
    assert fallback_restored.ema is False  # honest: raw params came back
    np.testing.assert_allclose(
        np.asarray(fallback["layers"]["wq"]),
        np.asarray(plain.params["layers"]["wq"]),
        rtol=0, atol=0,
    )

    # a plain state has no ema
    assert ema_params(plain) is None

    with pytest.raises(ValueError, match="decay"):
        with_ema(make_optimizer(1e-2), 1.5)


def test_inference_server_prefix_cache(run):
    """Prefix KV reuse: a second request sharing a long prompt prefix
    hits the cache, reuses most of the prefill, and produces EXACTLY
    the same tokens as an uncached server; LRU bounds the entries."""
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=128, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    cached = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=128,
        prefix_cache_entries=2,
    )
    plain = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=128)

    shared = list(range(1, 41))  # 40-token shared history
    turn2 = shared + [50, 51, 52]
    other = [9] * 40

    def fetch(server, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read().decode())["tokens"]

    async def scenario():
        import asyncio

        await cached.run()
        await plain.run()
        loop = asyncio.get_event_loop()

        async def gen(server, toks, **kw):
            body = {"tokens": [toks], "max_new_tokens": 8, **kw}
            return await loop.run_in_executor(
                None, lambda: fetch(server, body)
            )

        r1c = await gen(cached, shared)
        r1p = await gen(plain, shared)
        r2c = await gen(cached, turn2)   # shares the 40-token prefix
        r2p = await gen(plain, turn2)
        # sampled request through the prefix path too (same seed)
        r3c = await gen(cached, turn2, temperature=0.8, seed=7)
        r3p = await gen(plain, turn2, temperature=0.8, seed=7)
        # a third distinct prompt evicts the oldest entry (LRU cap 2)
        await gen(cached, other)
        stats = dict(cached.prefix_cache.stats)
        n_entries = len(cached.prefix_cache)
        await cached.stop()
        await plain.stop()
        return r1c, r1p, r2c, r2p, r3c, r3p, stats, n_entries

    import json

    r1c, r1p, r2c, r2p, r3c, r3p, stats, n_entries = run(
        scenario(), timeout=180
    )
    assert r1c == r1p, "cold-path output must match the uncached server"
    assert r2c == r2p, "prefix-hit output must match the uncached server"
    assert r3c == r3p, "sampled prefix-hit must match (same seed)"
    assert stats["hits"] >= 2, stats
    assert stats["tokens_reused"] >= 40, stats
    assert n_entries == 2  # LRU evicted down to the cap


def test_prefix_hit_honors_prefill_chunk():
    """An engine admission routes a long cached-hit suffix through
    the shared reuse_admission / extend_pieces protocol, so the
    documented O(prefill_chunk) activation bound covers the hit like
    the cold prompt — with byte-identical output to the unchunked
    engine, and hit/miss stats counted exactly once."""
    import containerpilot_tpu.models.decode as dec
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve_prefix import PrefixCache
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=128, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)

    pieces = []
    real_pieces = dec.extend_pieces

    def counting_pieces(params_, cache, suffix, cfg_, chunk_len):
        pieces.append((int(suffix.shape[1]), int(chunk_len)))
        return real_pieces(params_, cache, suffix, cfg_, chunk_len)

    dec.extend_pieces = counting_pieces
    try:
        shared = list(range(1, 41))       # 40-token history
        turn2 = shared + [50] * 24        # 24-token suffix > chunk 8
        outs = {}
        hit_pieces = {}
        for name, chunk_len in (("plain", 0), ("chunked", 8)):
            pc = PrefixCache(4)
            engine = SlotEngine(
                cfg, params, 128, slots=1, chunk=4,
                prefix_cache=pc, prefill_chunk=chunk_len,
            )
            try:
                cold = engine.submit(shared, 8).result(timeout=120)
                pieces.clear()  # isolate the HIT's extend pieces
                hit = engine.submit(turn2, 8).result(timeout=120)
            finally:
                engine.stop()
            hit_pieces[name] = list(pieces)
            outs[name] = [cold, hit]
            assert pc.stats["misses"] == 1, pc.stats
            assert pc.stats["hits"] == 1, pc.stats
            # suffix 24 buckets to 32 (BUCKET=16), so 32 of the 40
            # matched tokens are reused and 32 re-extend
            assert pc.stats["tokens_reused"] == 32
    finally:
        dec.extend_pieces = real_pieces
    assert outs["plain"] == outs["chunked"]
    # the chunked engine's hit actually took the bounded-piece path;
    # the unchunked engine's hit stayed on the one-shot extend
    assert hit_pieces == {"plain": [], "chunked": [(32, 8)]}


def test_chunked_prefill_matches_prefill():
    """Streaming the prompt through decode_chunk pieces must produce
    the same cache and last-position logits as one-shot prefill —
    dense, GQA, ragged final chunk, and windowed ring."""
    from containerpilot_tpu.models.decode import chunked_prefill, prefill

    for kw in (
        {},
        {"n_kv_heads": 2},
        {"window": 8},
    ):
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=128,
            max_seq_len=64, dtype=jnp.float32, flash_min_seq=0, **kw
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 23), 0, cfg.vocab_size, jnp.int32
        )  # 23 = 3 chunks of 7 + ragged 2
        ref_logits, ref_cache = prefill(params, tokens, cfg, 64)
        got_logits, got_cache = chunked_prefill(
            params, tokens, cfg, 64, chunk_len=7
        )
        np.testing.assert_allclose(
            np.asarray(got_logits), np.asarray(ref_logits),
            rtol=2e-3, atol=2e-3, err_msg=str(kw),
        )
        np.testing.assert_allclose(
            np.asarray(got_cache["k"]), np.asarray(ref_cache["k"]),
            rtol=1e-4, atol=1e-5, err_msg=str(kw),
        )
        assert int(got_cache["pos"]) == int(ref_cache["pos"]) == 23
        # decode continues identically from either cache
        from containerpilot_tpu.models.decode import decode_step

        la, _ = decode_step(params, got_cache, tokens[:, 0], cfg)
        lb, _ = decode_step(params, ref_cache, tokens[:, 0], cfg)
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=2e-3, atol=2e-3
        )
    with pytest.raises(ValueError, match="chunk_len"):
        chunked_prefill(params, tokens, cfg, 64, chunk_len=0)


def test_beam_search_width1_equals_greedy_and_exhaustive_optimum():
    """beam_width=1 reproduces greedy generate exactly; a beam wide
    enough to be exhaustive finds the brute-force argmax sequence."""
    from containerpilot_tpu.models.beam import beam_search
    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import forward

    cfg = TransformerConfig(
        vocab_size=8, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, flash_min_seq=0,
    )
    params = init_params(jax.random.PRNGKey(3), cfg)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)

    greedy = np.asarray(generate(params, prompt, cfg, 4, 32))[0]
    b1, _ = beam_search(params, prompt, cfg, 4, 32, beam_width=1)
    np.testing.assert_array_equal(np.asarray(b1), greedy)

    # exhaustive optimum over 2 steps: beam_width == vocab keeps every
    # possible first token, so no prefix of the best pair is pruned
    best_beam, best_score = beam_search(
        params, prompt, cfg, 2, 32, beam_width=8
    )

    def seq_logprob(cont):
        toks = jnp.asarray([[1, 2, 3] + list(cont)], jnp.int32)
        logits = forward(params, toks, cfg)
        logp = jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1
        )
        return sum(
            float(logp[0, 2 + i, cont[i]]) for i in range(len(cont))
        )

    brute = max(
        ((a, b) for a in range(8) for b in range(8)),
        key=seq_logprob,
    )
    assert tuple(np.asarray(best_beam)) == brute
    np.testing.assert_allclose(best_score, seq_logprob(brute), rtol=1e-5)


def test_beam_search_eos_and_validation():
    """Finished beams freeze (pad after eos, score keeps competing);
    invalid arguments fail loudly."""
    from containerpilot_tpu.models.beam import beam_search

    cfg = TransformerConfig(
        vocab_size=16, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, flash_min_seq=0,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[1, 2]], jnp.int32)
    # beam_width=1 follows the greedy path exactly, so declaring the
    # greedy second token as eos GUARANTEES the freeze logic fires
    from containerpilot_tpu.models.decode import generate

    greedy = list(np.asarray(generate(params, prompt, cfg, 6, 32))[0])
    eos = int(greedy[1])
    toks, _ = beam_search(
        params, prompt, cfg, 6, 32, beam_width=1, eos_id=eos, pad_id=0
    )
    toks = list(np.asarray(toks))
    assert eos in toks, (toks, greedy)
    after = toks[toks.index(eos) + 1:]
    # eos fires by step 2 at the latest, so pads definitely follow
    assert len(after) >= 4 and all(t == 0 for t in after), toks
    with pytest.raises(ValueError, match="beam_width"):
        beam_search(params, prompt, cfg, 4, 32, beam_width=0)
    with pytest.raises(ValueError, match="one prompt"):
        beam_search(
            params, jnp.ones((2, 3), jnp.int32), cfg, 4, 32
        )
    with pytest.raises(ValueError, match="sliding-window"):
        import dataclasses

        beam_search(
            params, prompt, dataclasses.replace(cfg, window=8), 4, 32
        )


def test_inference_server_beam_search(run):
    """/v1/generate beam_width: beam-1 equals greedy over HTTP; wider
    beams return a (length-trimmed) deterministic result; invalid
    combinations 422."""
    import urllib.error
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=64)

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        async def gen(body):
            return await loop.run_in_executor(None, lambda: fetch(body))

        base = {"tokens": [[1, 2, 3]], "max_new_tokens": 6}
        greedy = await gen(base)
        b1 = await gen({**base, "beam_width": 1})
        b4a = await gen({**base, "beam_width": 4})
        b4b = await gen({**base, "beam_width": 4})
        bad = await gen({**base, "beam_width": 4, "temperature": 0.7})
        await server.stop()
        return greedy, b1, b4a, b4b, bad

    import json

    greedy, b1, b4a, b4b, bad = run(scenario(), timeout=180)
    assert greedy[0] == b1[0] == 200
    assert b1[1]["tokens"] == greedy[1]["tokens"]
    assert b4a[0] == 200 and b4a[1] == b4b[1]  # deterministic
    assert bad[0] == 422 and "deterministic" in bad[1]


def test_async_checkpoint_commits_and_restores(tmp_path):
    """save_checkpoint(wait=False) returns before the disk commit but
    captures the state at call time: stepping (and donating) right
    after the call cannot corrupt the write, and after
    wait_for_checkpoints the restore equals the saved-step state."""
    from containerpilot_tpu.parallel import (
        abstract_train_state,
        restore_checkpoint,
        save_checkpoint,
        wait_for_checkpoints,
    )

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:8])
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    step = make_train_step(cfg, mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size, jnp.int32
    )
    state, _ = step(state, tokens)
    saved_wq = np.asarray(state.params["layers"]["wq"]).copy()
    save_checkpoint(str(tmp_path), 1, state, wait=False)
    # keep training immediately — the donated buffers get overwritten
    # while the background write is (possibly) still in flight
    for _ in range(3):
        state, _ = step(state, tokens)
    assert not np.allclose(
        np.asarray(state.params["layers"]["wq"]), saved_wq
    )
    wait_for_checkpoints()
    abstract = abstract_train_state(jax.random.PRNGKey(0), cfg, mesh)
    restored = restore_checkpoint(str(tmp_path), abstract)
    assert int(restored.step) == 1
    np.testing.assert_array_equal(
        np.asarray(restored.params["layers"]["wq"]), saved_wq
    )


def test_kv_int8_cache_decode_parity():
    """int8 KV cache: half the bytes, decode stays within quantization
    tolerance of the f32-cache path — dense, GQA, windowed ring, and
    chunked decode; greedy token-level agreement end-to-end."""
    from containerpilot_tpu.models.decode import (
        decode_chunk,
        decode_step,
        generate,
        prefill,
    )
    import dataclasses

    for kw in ({}, {"n_kv_heads": 2}, {"window": 8}):
        cfg = TransformerConfig(
            vocab_size=64, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            max_seq_len=64, dtype=jnp.float32, flash_min_seq=0, **kw
        )
        cfg_q = dataclasses.replace(cfg, kv_int8=True)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab_size, jnp.int32
        )
        ref_logits, ref_cache = prefill(params, tokens[:, :10], cfg, 48)
        q_logits, q_cache = prefill(params, tokens[:, :10], cfg_q, 48)
        assert q_cache["k"].dtype == jnp.int8
        assert "k_scale" in q_cache
        # bytes: int8 k/v + f32 scales ~ half the f32 k/v
        f32_bytes = ref_cache["k"].nbytes + ref_cache["v"].nbytes
        q_bytes = sum(
            q_cache[n].nbytes for n in
            ("k", "v", "k_scale", "v_scale")
        )
        assert q_bytes < f32_bytes / 2 + 1
        np.testing.assert_allclose(
            np.asarray(q_logits), np.asarray(ref_logits),
            rtol=0.05, atol=0.05, err_msg=str(kw),
        )
        # chunked decode through the quantized cache
        la, ca = decode_chunk(params, ref_cache, tokens[:, 10:14], cfg)
        lb, cb = decode_chunk(params, q_cache, tokens[:, 10:14], cfg_q)
        np.testing.assert_allclose(
            np.asarray(lb), np.asarray(la), rtol=0.08, atol=0.08,
            err_msg=str(kw),
        )
        for i in range(14, 20):
            la, ca = decode_step(params, ca, tokens[:, i], cfg)
            lb, cb = decode_step(params, cb, tokens[:, i], cfg_q)
            np.testing.assert_allclose(
                np.asarray(lb), np.asarray(la), rtol=0.1, atol=0.1,
                err_msg=f"{kw} position {i}",
            )
        # greedy generations agree token-for-token on this scale of
        # model (logit gaps dwarf the quantization noise)
        ga = generate(params, tokens[:, :10], cfg, 8, 48)
        gb = generate(params, tokens[:, :10], cfg_q, 8, 48)
        np.testing.assert_array_equal(
            np.asarray(ga), np.asarray(gb), err_msg=str(kw)
        )


def test_inference_server_text_completions(run):
    """The text surface (--text): /v1/completions encodes the prompt
    through the byte tokenizer, decodes generated ids back to text,
    and agrees exactly with the token-level /v1/generate path."""
    import urllib.error
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer
    from containerpilot_tpu.workload.text import ByteTokenizer

    cfg = TransformerConfig(
        vocab_size=512, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=64, text=True
    )
    tok = ByteTokenizer(cfg.vocab_size)

    def fetch(path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()
        comp = await loop.run_in_executor(
            None,
            lambda: fetch(
                "/v1/completions",
                {"prompt": "hi", "max_new_tokens": 6},
            ),
        )
        # token-level equivalent: same encoding, explicit EOS default
        gen = await loop.run_in_executor(
            None,
            lambda: fetch(
                "/v1/generate",
                {"tokens": [tok.encode("hi")], "max_new_tokens": 6,
                 "eos_id": tok.EOS},
            ),
        )
        bad = await loop.run_in_executor(
            None, lambda: fetch("/v1/completions", {"prompt": ""})
        )
        too_long = await loop.run_in_executor(
            None,
            lambda: fetch("/v1/completions",
                          {"prompt": "x", "max_new_tokens": 999}),
        )
        # a server built with no slots argument streams: the SSE
        # deltas concatenate to the buffered answer
        def stream():
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/completions",
                data=json.dumps({"prompt": "hi", "max_new_tokens": 6,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert resp.headers["Content-Type"] == "text/event-stream"
                return [
                    json.loads(frame[len("data: "):])
                    for frame in resp.read().decode().split("\n\n")
                    if frame
                ]

        streamed = await loop.run_in_executor(None, stream)
        await server.stop()
        return comp, gen, bad, too_long, streamed

    import json

    comp, gen, bad, too_long, streamed = run(scenario(), timeout=120)
    assert comp[0] == 200, comp
    assert gen[0] == 200, gen
    assert comp[1]["tokens"] == gen[1]["tokens"][0]
    assert comp[1]["text"] == tok.decode(comp[1]["tokens"])
    assert bad[0] == 422
    assert too_long[0] == 422
    assert streamed[-1]["done"] is True
    assert sum(
        (e["tokens"] for e in streamed if "tokens" in e), []
    ) == comp[1]["tokens"]
    assert "".join(e.get("text", "") for e in streamed) == comp[1]["text"]


def test_serve_text_requires_byte_vocab():
    """--text with a vocab too small for the byte tokenizer fails at
    construction, not as request-time 500s."""
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="vocab_size >= 259"):
        InferenceServer(
            cfg, params, "127.0.0.1", 0, max_len=32, text=True
        )


def test_serve_cli_text_flag():
    """The --text flag exists and routes into InferenceServer."""
    from containerpilot_tpu.workload.serve_cli import build_arg_parser

    args = build_arg_parser().parse_args(["--text", "--vocab", "512"])
    assert args.text is True and args.vocab == 512
    assert build_arg_parser().parse_args([]).text is False


def test_remat_policies_equivalent():
    """remat=True (full), remat="dots" (keep matmul outputs), and
    remat=False (plus the "full"/"none" string aliases) trade memory
    for recompute only — loss and grads must agree to tight numerical
    tolerance across policies."""
    import numpy as np

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        loss_fn,
    )

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 17), 0, 64, jnp.int32
    )
    results = {}
    for remat in (True, "dots", False, "full", "none"):
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
            max_seq_len=16, dtype=jnp.float32, remat=remat,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        loss, grads = jax.jit(
            jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg))
        )(params)
        results[str(remat)] = (
            float(loss),
            [np.asarray(g) for g in jax.tree.leaves(grads)],
        )
    # the string aliases must be exact synonyms of their booleans
    for alias, boolean in (("full", "True"), ("none", "False")):
        assert results[alias][0] == results[boolean][0]
        for a, b in zip(results[alias][1], results[boolean][1]):
            np.testing.assert_array_equal(a, b)
    base_loss, base_grads = results["True"]
    for name, (loss, grads) in results.items():
        np.testing.assert_allclose(loss, base_loss, rtol=1e-6, err_msg=name)
        assert len(grads) == len(base_grads)
        for got, want in zip(grads, base_grads):
            np.testing.assert_allclose(
                got, want, rtol=1e-5, atol=1e-6, err_msg=name
            )


def test_remat_invalid_value_rejected_at_construction():
    with pytest.raises(ValueError, match="remat"):
        TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
            max_seq_len=16, remat="Dots",
        )


def test_tensor_parallel_generate_parity():
    """Serving TP: generate with params sharded model-parallel over
    the 8-device CPU mesh matches the single-device output exactly —
    greedy and seeded-sampled. XLA inserts the collectives; the decode
    scan, KV cache, and sampling all ride the sharding."""
    import numpy as np

    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.parallel import (
        MeshPlan,
        make_mesh,
        shard_params,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_heads=8, n_layers=2, d_ff=128,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(jax.devices()[:8], plan=MeshPlan(data=1, model=8))
    sharded = shard_params(params, mesh, cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(7), (2, 6), 0, cfg.vocab_size, jnp.int32
    )
    for kwargs in (
        {"temperature": 0.0},
        {"temperature": 0.8, "rng": jax.random.PRNGKey(3), "top_k": 8},
    ):
        single = generate(
            params, prompt, cfg, max_new_tokens=8, max_len=32, **kwargs
        )
        tp = generate(
            sharded, prompt, cfg, max_new_tokens=8, max_len=32, **kwargs
        )
        np.testing.assert_array_equal(
            np.asarray(single), np.asarray(tp), err_msg=str(kwargs)
        )


def test_inference_server_reports_mesh(run):
    """/v1/model surfaces the device mesh TP-sharded params live on,
    and serving works end-to-end on sharded params."""
    import json
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.parallel import (
        MeshPlan,
        make_mesh,
        shard_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_heads=8, n_layers=1, d_ff=128,
        max_seq_len=32, dtype=jnp.float32,
    )
    mesh = make_mesh(jax.devices()[:8], plan=MeshPlan(data=1, model=8))
    params = shard_params(
        init_params(jax.random.PRNGKey(0), cfg), mesh, cfg
    )
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"} if body else {},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read().decode())

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()
        info = await loop.run_in_executor(
            None, lambda: fetch("/v1/model")
        )
        gen = await loop.run_in_executor(
            None,
            lambda: fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 4},
            ),
        )
        await server.stop()
        return info, gen

    info, gen = run(scenario())
    assert info["mesh"] == {"data": 1, "model": 8}
    assert len(gen["tokens"][0]) == 4


@pytest.mark.parametrize("seq", [16, 17])  # 17: chunk-padding path
def test_chunked_loss_matches_whole_logits(seq):
    """loss_chunk streams the vocab projection in pieces; loss and
    grads must match the whole-logits loss to f32 tolerance, including
    when the sequence does not divide by the chunk."""
    import dataclasses

    base = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, seq + 1), 0, base.vocab_size,
        jnp.int32,
    )
    params = init_params(jax.random.PRNGKey(0), base)
    whole_loss, whole_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, tokens, base))
    )(params)
    chunked = dataclasses.replace(base, loss_chunk=8)
    c_loss, c_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, tokens, chunked))
    )(params)
    np.testing.assert_allclose(
        float(c_loss), float(whole_loss), rtol=1e-6
    )
    for got, want in zip(
        jax.tree.leaves(c_grads), jax.tree.leaves(whole_grads)
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-6
        )


def test_generate_stop_sequences(run):
    """'stop' trims at the earliest stop-sequence occurrence,
    excluding the stop itself; invalid specs 422."""
    import json
    import urllib.error
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=512, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=64, text=True
    )

    def fetch(path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            # free-run greedy to learn the deterministic continuation
            _s, free = fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 8},
            )
            row = free["tokens"][0]
            # stop at the first token whose value hasn't occurred
            # before it: output = everything before that position
            k = next(
                (i for i in range(1, len(row))
                 if row[i] not in row[:i]),
                None,  # all-repeats continuation: nothing to stop on
            )
            if k is None:
                return row, None, (200, {"tokens": [row]}), \
                    (200, {"tokens": [row]}), 422, 422
            s1, stopped = fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 8,
                 "stop": [[row[k]]]},
            )
            # a stop that never occurs changes nothing
            s2, untouched = fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 8,
                 "stop": [[cfg.vocab_size - 1, cfg.vocab_size - 2]]},
            )
            s3, bad = fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 4,
                 "stop": [[]]},
            )
            s4, bad_type = fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 4,
                 "stop": "nope"},
            )
            return row, k, (s1, stopped), (s2, untouched), s3, s4

        out = await loop.run_in_executor(None, go)
        await server.stop()
        return out

    row, k, (s1, stopped), (s2, untouched), s3, s4 = run(scenario())
    if k is None:
        pytest.skip("greedy continuation has no first-unique token")
    assert s1 == 200 and stopped["tokens"][0] == row[:k]
    assert s2 == 200 and untouched["tokens"][0] == row
    assert s3 == 422 and s4 == 422


def test_completions_stop_strings(run):
    """The text surface takes stop STRINGS and excludes them."""
    import json
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer
    from containerpilot_tpu.workload.text import ByteTokenizer

    cfg = TransformerConfig(
        vocab_size=512, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=64, text=True
    )
    tok = ByteTokenizer(cfg.vocab_size)

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read().decode())

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            free = fetch({"prompt": "ab", "max_new_tokens": 6})
            # stop at the text of the 2nd+3rd generated bytes
            stop_text = tok.decode(free["tokens"][1:3])
            # only meaningful when the text round-trips to exactly
            # those ids (specials/out-of-range bytes are dropped by
            # decode and would test a DIFFERENT stop sequence)
            if (
                not stop_text
                or tok.encode(stop_text, bos=False)
                != free["tokens"][1:3]
            ):
                return free, None, None
            stopped = fetch(
                {"prompt": "ab", "max_new_tokens": 6,
                 "stop": stop_text}
            )
            return free, stop_text, stopped

        out = await loop.run_in_executor(None, go)
        await server.stop()
        return out

    free, stop_text, stopped = run(scenario())
    if stop_text is not None:
        assert stopped["tokens"] == free["tokens"][:1]
        assert stop_text not in stopped["text"]


def test_min_new_tokens_suppresses_early_eos():
    """min_new_tokens masks the eos logit for a row's first N samples
    on the compiled path — greedy AND sampled — so answers can be
    floored; min_new=0 leaves numerics bitwise-unchanged."""
    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[3, 5, 7]], jnp.int32)

    baseline = np.asarray(generate(
        params, prompt, cfg, max_new_tokens=8, max_len=32
    ))[0]
    eos = int(baseline[1])  # would stop after 2 tokens

    zero = np.asarray(generate(
        params, prompt, cfg, max_new_tokens=8, max_len=32,
        min_new_tokens=0, eos_id=eos,
    ))[0]
    floored = np.asarray(generate(
        params, prompt, cfg, max_new_tokens=8, max_len=32,
        min_new_tokens=5, eos_id=eos,
    ))[0]
    # min_new=0: the early eos stands (token 1), pads follow
    assert zero[1] == eos
    # floored: samples 0..4 are eos-free by construction
    assert not (floored[:5] == eos).any()

    # sampled path too, per-row: row 0 floored, row 1 free
    two = jnp.asarray([[3, 5, 7], [3, 5, 7]], jnp.int32)
    out = np.asarray(generate(
        params, two, cfg, max_new_tokens=8, max_len=32,
        temperature=0.9, rng=jax.random.PRNGKey(5),
        eos_id=eos, min_new_tokens=[6, 0],
    ))
    assert not (out[0, :6] == eos).any()

    with pytest.raises(ValueError, match="min_new_tokens"):
        generate(
            params, prompt, cfg, max_new_tokens=4, max_len=32,
            min_new_tokens=9,
        )


def test_min_new_tokens_over_http(run):
    """The serving knob floors answers through the batcher path and
    422s out-of-range values."""
    import json
    import urllib.error
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            _s, free = fetch(
                {"tokens": [[1, 2, 3]], "max_new_tokens": 8}
            )
            eos = free["tokens"][0][1]
            s1, stopped = fetch(
                {"tokens": [[1, 2, 3]], "max_new_tokens": 8,
                 "eos_id": eos}
            )
            s2, floored = fetch(
                {"tokens": [[1, 2, 3]], "max_new_tokens": 8,
                 "eos_id": eos, "min_new_tokens": 5}
            )
            s3, bad = fetch(
                {"tokens": [[1, 2, 3]], "max_new_tokens": 4,
                 "min_new_tokens": 9}
            )
            return eos, (s1, stopped), (s2, floored), s3

        out = await loop.run_in_executor(None, go)
        await server.stop()
        return out

    eos, (s1, stopped), (s2, floored), s3 = run(scenario())
    assert s1 == 200 and len(stopped["tokens"][0]) == 2
    assert s2 == 200
    row = floored["tokens"][0]
    assert len(row) >= 5 and eos not in row[:5]
    assert s3 == 422


def test_inference_server_metrics_endpoint(run):
    """GET /metrics: Prometheus exposition with request counts,
    latency histogram, and post-trim token accounting."""
    import json
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"} if body else {},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            fetch("/v1/generate",
                  {"tokens": [[1, 2, 3]], "max_new_tokens": 6})
            fetch("/v1/generate",
                  {"tokens": [[4, 5]], "max_new_tokens": 4})
            return fetch("/metrics")

        text = await loop.run_in_executor(None, go)
        await server.stop()
        return text

    text = run(scenario())
    assert (
        'containerpilot_serve_requests_total{'
        'code="200",endpoint="generate"} 2.0' in text
    )
    assert "containerpilot_serve_generated_tokens_total 10.0" in text
    assert (
        'containerpilot_serve_request_seconds_count{'
        'endpoint="generate"} 2.0' in text
    )
    # the loopcheck sentinel surfaces on every replica (analysis/
    # loopcheck.py; docs/70 has the runbook for reading it)
    assert 'cp_loop_lag_ms{stat="max"}' in text
    assert 'cp_loop_lag_ms{stat="p99"}' in text


def test_generate_logprobs_echo(run):
    """{"logprobs": true} echoes per-token logprobs of the trimmed
    generated ids via one teacher-forced pass — must match /v1/score
    on prompt+generated at the generated positions (decode == forward
    is the tested invariant that makes this exact)."""
    import json
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read().decode())

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            prompt = [1, 2, 3]
            gen = fetch("/v1/generate", {
                "tokens": [prompt], "max_new_tokens": 6,
                "logprobs": True,
            })
            row = gen["tokens"][0]
            score = fetch("/v1/score", {"tokens": [prompt + row]})
            # rows of different trimmed lengths share one echo batch
            eos = row[1]
            two = fetch("/v1/generate", {
                "tokens": [prompt, [4, 5, 6]], "max_new_tokens": 6,
                "eos_id": eos, "logprobs": True,
            })
            return gen, row, score, two

        out = await loop.run_in_executor(None, go)
        await server.stop()
        return out

    gen, row, score, two = run(scenario())
    lps = gen["logprobs"][0]
    assert len(lps) == len(row) and all(x <= 0.0 for x in lps)
    # the echo is exactly the score endpoint's tail slice
    assert lps == score["logprobs"][0][-len(row):]
    for toks, lp_row in zip(two["tokens"], two["logprobs"]):
        assert len(toks) == len(lp_row)


def test_penalties_suppress_repetition(run):
    """presence/frequency penalties subtract from generated-token
    logits across the compiled paths; zero penalties are bitwise
    neutral; out-of-range 422s."""
    import json
    import urllib.error
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            base = {"tokens": [[1, 2, 3]], "max_new_tokens": 8}
            _s, plain = fetch(base)
            _s, zero = fetch({**base, "presence_penalty": 0.0,
                              "frequency_penalty": 0.0})
            s1, norep = fetch({**base, "frequency_penalty": 50.0})
            s2, bad = fetch({**base, "presence_penalty": 1000.0})
            return plain, zero, (s1, norep), s2

        out = await loop.run_in_executor(None, go)
        await server.stop()
        return out

    plain, zero, (s1, norep), s2 = run(scenario())
    assert zero["tokens"] == plain["tokens"]
    row = norep["tokens"][0]
    assert s1 == 200 and len(set(row)) == len(row)
    assert s2 == 422


def test_logit_bias_math_and_validation():
    """apply_logit_bias: -1 slots are bitwise-neutral, entries add
    exactly; normalize_logit_bias rejects the same bounds the HTTP
    layer documents."""
    import numpy as np

    from containerpilot_tpu.models.decode import (
        BIAS_SLOTS,
        BIAS_SLOTS_MAX,
        apply_logit_bias,
        normalize_logit_bias,
    )

    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=16, dtype=jnp.float32,
    )
    logits = jnp.arange(2 * 32, dtype=jnp.float32).reshape(2, 32)
    idx, val = normalize_logit_bias(
        cfg, 2, [{5: 3.0, 7: -2.0}, None]
    )
    out = apply_logit_bias(logits, jnp.asarray(idx), jnp.asarray(val))
    expect = np.array(logits)  # writable copy
    expect[0, 5] += 3.0
    expect[0, 7] += -2.0
    np.testing.assert_array_equal(np.asarray(out), expect)
    # all-empty bias is bitwise-neutral
    idx0, val0 = normalize_logit_bias(cfg, 2, None)
    np.testing.assert_array_equal(
        np.asarray(
            apply_logit_bias(logits, jnp.asarray(idx0),
                             jnp.asarray(val0))
        ),
        np.asarray(logits),
    )
    for bad in (
        {99: 1.0},             # out of vocab
        {3: 500.0},            # out of range
        {3: 1.0, "x": 1.0},    # unparseable key: ValueError, not
        # a raw TypeError out of sorted() on mixed key types
    ):
        with pytest.raises(ValueError):
            normalize_logit_bias(cfg, 1, bad)
    # str keys are OpenAI's JSON wire form; mixing them with int
    # keys must coerce, not blow up sorting
    idx_m, _val_m = normalize_logit_bias(cfg, 1, {"5": 2.0, 3: 1.0})
    assert sorted(int(i) for i in idx_m[0] if i >= 0) == [3, 5]
    # BIAS_SLOTS is a fast path, not the cap: one entry over it
    # jumps to the wide static table (OpenAI's 300); one entry over
    # THAT is the real 422
    big = TransformerConfig(
        vocab_size=512, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=16, dtype=jnp.float32,
    )
    assert normalize_logit_bias(cfg, 1, {3: 1.0})[0].shape == \
        (1, BIAS_SLOTS)
    idx_w, val_w = normalize_logit_bias(
        big, 1, {i: 1.0 for i in range(BIAS_SLOTS + 1)}
    )
    assert idx_w.shape == (1, BIAS_SLOTS_MAX)
    assert int((idx_w[0] >= 0).sum()) == BIAS_SLOTS + 1
    with pytest.raises(ValueError):
        normalize_logit_bias(
            big, 1, {i: 1.0 for i in range(BIAS_SLOTS_MAX + 1)}
        )


def test_logit_bias_forces_and_bans_across_paths():
    """OpenAI semantics end-to-end: +100 effectively forces a token
    every step, -100 bans one, greedy and sampled — and the slot
    engine's emission matches generate's with the same bias."""
    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)

    forced = generate(
        params, prompt, cfg, 6, 32, logit_bias={9: 100.0}
    )
    assert [int(t) for t in forced[0]] == [9] * 6

    plain = [int(t) for t in generate(params, prompt, cfg, 6, 32)[0]]
    banned_id = plain[0]
    banned = generate(
        params, prompt, cfg, 6, 32, logit_bias={banned_id: -100.0}
    )
    assert banned_id not in [int(t) for t in banned[0]]

    # sampled path: the ban holds under temperature too
    rng = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(7), 0)])
    sampled = generate(
        params, prompt, cfg, 8, 32, temperature=1.2, rng=rng,
        logit_bias={banned_id: -100.0},
    )
    assert banned_id not in [int(t) for t in sampled[0]]

    # slot engine parity with the same bias (server key convention)
    eng = SlotEngine(cfg, params, 32, slots=2, chunk=3)
    try:
        got = eng.submit(
            [1, 2, 3], max_new=6, logit_bias={9: 100.0}
        ).result(timeout=120)
        assert got == [9] * 6
        ref = generate(
            params, prompt, cfg, 6, 32,
            rng=jnp.stack(
                [jax.random.fold_in(jax.random.PRNGKey(0), 0)]
            ),
            logit_bias={banned_id: -100.0},
        )
        got2 = eng.submit(
            [1, 2, 3], max_new=6, logit_bias={banned_id: -100.0}
        ).result(timeout=120)
        assert got2 == [int(t) for t in ref[0]]
        # > BIAS_SLOTS entries ride the wide static table (OpenAI
        # allows 300): 20 banned ids hold on both paths, outputs
        # byte-identical
        wide = {i: -100.0 for i in range(20)}
        ref_w = generate(
            params, prompt, cfg, 6, 32,
            rng=jnp.stack(
                [jax.random.fold_in(jax.random.PRNGKey(0), 0)]
            ),
            logit_bias=wide,
        )
        got_w = eng.submit(
            [1, 2, 3], max_new=6, logit_bias=wide
        ).result(timeout=120)
        assert got_w == [int(t) for t in ref_w[0]]
        assert all(t >= 20 for t in got_w)
    finally:
        eng.stop()


def test_n_samples_over_http(run):
    """OpenAI's n: one prompt, n independent samples as one batched
    device call — row i draws from fold_in(seed, i), so each row
    byte-matches the model-level generate with that key; greedy rows
    are identical by definition; bad compositions 422."""
    import json
    import urllib.error
    import urllib.request

    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            base = {"tokens": [[1, 2, 3]], "max_new_tokens": 6}
            s1, sampled = fetch({
                **base, "n": 3, "temperature": 0.9, "seed": 11,
            })
            s2, greedy = fetch({**base, "n": 2})
            s3, _ = fetch({**base, "n": 99})
            s4, _ = fetch({
                "tokens": [[1, 2], [3, 4]], "max_new_tokens": 4,
                "n": 2,
            })
            s5, _ = fetch({**base, "n": 2, "beam_width": 2})
            s6, stream_err = fetch({**base, "n": 2, "stream": True})
            return (s1, sampled), (s2, greedy), s3, s4, s5, \
                (s6, stream_err)

        out = await loop.run_in_executor(None, go)
        await server.stop()
        return out

    ((s1, sampled), (s2, greedy), s3, s4, s5,
     (s6, stream_err)) = run(scenario())
    assert s1 == 200 and len(sampled["tokens"]) == 3
    # row i == model-level generate with the per-row key convention
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    for i, row in enumerate(sampled["tokens"]):
        ref = generate(
            params, prompt, cfg, 6, 32, temperature=0.9,
            rng=jnp.stack(
                [jax.random.fold_in(jax.random.PRNGKey(11), i)]
            ),
        )
        assert row == [int(t) for t in ref[0]], i
    # independent keys actually diversify (not a fixed guarantee in
    # general, but deterministic for this seed/model)
    assert len({tuple(r) for r in sampled["tokens"]}) > 1
    assert s2 == 200 and greedy["tokens"][0] == greedy["tokens"][1]
    assert s3 == s4 == s5 == 422
    # the n+stream 422 names the actual conflict, not the row count
    assert s6 == 422 and "n does not compose with stream" in stream_err


def test_logit_bias_over_http(run):
    """/v1/generate accepts OpenAI's string-keyed logit_bias through
    the batcher path; bad requests 422; beam rejects it."""
    import json
    import urllib.error
    import urllib.request

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()

        def go():
            base = {"tokens": [[1, 2, 3]], "max_new_tokens": 5}
            s_plain, plain = fetch(base)
            s_force, forced = fetch(
                {**base, "logit_bias": {"9": 100}}
            )
            # OpenAI semantics: an empty map is a no-op, not an error
            s_empty, empty = fetch({**base, "logit_bias": {}})
            s_bad1, _ = fetch({**base, "logit_bias": {"999": 1}})
            s_bad2, _ = fetch({**base, "logit_bias": {"3": 1000}})
            s_bad3, _ = fetch({**base, "logit_bias": []})
            s_beam, beam_err = fetch(
                {**base, "logit_bias": {"9": 1}, "beam_width": 2}
            )
            return (s_plain, plain), (s_force, forced), \
                (s_empty, empty), s_bad1, s_bad2, s_bad3, \
                (s_beam, beam_err)

        out = await loop.run_in_executor(None, go)
        await server.stop()
        return out

    ((s_plain, plain), (s_force, forced), (s_empty, empty), s_bad1,
     s_bad2, s_bad3, (s_beam, beam_err)) = run(scenario())
    assert s_force == 200 and forced["tokens"][0] == [9] * 5
    assert s_plain == s_empty == 200
    assert empty["tokens"] == plain["tokens"]
    assert s_bad1 == s_bad2 == s_bad3 == 422
    assert s_beam == 422 and "beam" in beam_err


def test_fuzz_generate_knob_combinations():
    """Random combinations of every sampling knob against the
    invariants that must hold regardless: output shape, pads after
    eos, min_new eos suppression, seed determinism, and in-vocab ids
    (penalty EFFECTS are asserted by their dedicated tests; here the
    knobs only widen the combination space). Knob values are drawn so
    the combos reuse a small
    set of compiled programs (max_new fixed; greedy/filtered/
    penalized/biased each toggled)."""
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np

    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = random.Random(7)
    max_new = 8

    for trial in range(12):
        greedy = rng.random() < 0.4
        kw = {
            "temperature": 0.0 if greedy else rng.uniform(0.3, 1.5),
            "top_k": rng.choice([0, 0, 5, 40]),
            "top_p": rng.choice([0.0, 0.0, 0.7, 0.95]),
            "eos_id": rng.choice([-1, rng.randrange(cfg.vocab_size)]),
            "min_new_tokens": rng.choice([0, 0, 3]),
            "presence_penalty": rng.choice([0.0, 0.0, 1.5]),
            "frequency_penalty": rng.choice([0.0, 0.0, 2.0]),
            "logit_bias": rng.choice([
                None, None,
                {rng.randrange(cfg.vocab_size): rng.choice([-100.0, -5.0, 5.0])},
            ]),
        }
        prompt = jnp.asarray(
            [[rng.randrange(cfg.vocab_size) for _ in range(4)]],
            jnp.int32,
        )
        key = jax.random.PRNGKey(trial)
        out1 = np.asarray(generate(
            params, prompt, cfg, max_new, 32, rng=key, **kw
        ))[0]
        out2 = np.asarray(generate(
            params, prompt, cfg, max_new, 32, rng=key, **kw
        ))[0]
        label = f"trial {trial}: {kw}"
        assert out1.shape == (max_new,), label
        assert (out1 == out2).all(), f"nondeterministic: {label}"
        assert ((out1 >= 0) & (out1 < cfg.vocab_size)).all(), label
        eos = kw["eos_id"]
        if eos >= 0:
            hits = np.flatnonzero(out1 == eos)
            if hits.size:
                first = int(hits[0])
                # eos never before the floor...
                assert first >= kw["min_new_tokens"], label
                # ...and everything after the first eos is pad (0)
                assert (out1[first + 1:] == 0).all(), label
        bias = kw["logit_bias"]
        if bias:
            ((tok, val),) = bias.items()
            if val <= -100.0 and tok != 0 and tok != eos:
                # a full ban keeps the token out (pad 0 and eos fill
                # rows for other reasons, so those ids are exempt)
                assert tok not in out1, label
