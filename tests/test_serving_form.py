"""The serving form (models/transformer.py serving_params, applied by
workload/serve_cli.py load_model): a replica's weights are resident
in the compute dtype, and the programs read either form to the same
bits. Tiny model on the CPU backend.
"""
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models.quantized import (
    param_bytes,
    quantize_model_params,
    resident_weights,
)
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    serving_params,
)
from containerpilot_tpu.workload import serve_cli
from containerpilot_tpu.workload.modelcfg import derive_d_ff

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(ROOT, "benchmark", "tests", "toy")
MODEL_FLAGS = ["--max-len", "48", "--d-model", "32", "--n-layers", "2",
               "--n-heads", "4", "--n-kv-heads", "2", "--vocab", "64"]


def _cfg():
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=derive_d_ff(32), max_seq_len=48,
    )


def _load(*flags):
    return serve_cli.load_model(
        serve_cli.build_arg_parser().parse_args([*MODEL_FLAGS, *flags]))


def _rounded(tree, dtype=jnp.bfloat16):
    return jax.tree.map(lambda x: np.asarray(x.astype(dtype)), tree)


def _assert_same_trees(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_serving_params_rounds_each_leaf_once_and_consumes_the_tree():
    cfg = _cfg()
    master = init_params(jax.random.PRNGKey(0), cfg)
    want = _rounded(master)
    float32_leaves = jax.tree.leaves(master)
    served = serving_params(master, cfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(served))
    _assert_same_trees(served, want)
    # the float32 leaf goes as soon as its rounded copy exists
    assert all(leaf.is_deleted() for leaf in float32_leaves)
    assert param_bytes(served) * 2 == sum(x.size * 4 for x in float32_leaves)
    # the form is a fixed point: a bf16 leaf is handed back as it is
    again = serving_params(served, cfg)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(served)):
        assert a is b and not a.is_deleted()


def test_serving_params_leaves_integer_leaves_alone():
    cfg = _cfg()
    tree = {"w": jnp.ones((4, 4), jnp.float32), "ids": jnp.arange(4)}
    served = serving_params(tree, cfg)
    assert served["w"].dtype == jnp.bfloat16
    assert served["ids"].dtype == jnp.int32 and not served["ids"].is_deleted()


@pytest.mark.parametrize("program", ["prefill", "decode_chunk", "forward"])
def test_programs_read_either_form_to_the_same_bits(program):
    """``.astype(cfg.dtype)`` on a bf16 leaf is the identity, so the
    float32 tree and its serving form give every program the same
    operands: logits and cache equal bit for bit."""
    from containerpilot_tpu.models.decode import decode_chunk, prefill
    from containerpilot_tpu.models.transformer import forward

    cfg = _cfg()
    master = init_params(jax.random.PRNGKey(0), cfg)
    served = serving_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size, jnp.int32)

    def run(params):
        if program == "forward":
            return forward(params, tokens, cfg)
        logits, cache = prefill(params, tokens[:, :6], cfg, 16)
        if program == "prefill":
            return logits, cache
        return decode_chunk(params, cache, tokens[:, 6:], cfg)

    want, got = jax.jit(run)(master), jax.jit(run)(served)
    _assert_same_trees(got, want)


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=60) as resp:
        return json.loads(resp.read())


def test_a_replica_from_load_model_serves_what_the_float32_tree_serves(run):
    """``load_model``'s replica against ``InferenceServer`` handed the
    float32 tree of the same seed: the same greedy tokens, the same
    echoed logprobs and the same scores, and ``/v1/model`` ``weights``
    says which form each holds."""
    import asyncio

    from containerpilot_tpu.workload.serve import InferenceServer

    cfg, served, _mesh = _load()
    master = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 9, 2, 40, 7], [3, 3, 61, 8, 1, 30, 12]]

    async def drive(params):
        server = InferenceServer(
            cfg, params, "127.0.0.1", 0, max_len=48, slots=2, slot_chunk=4)
        await server.run()
        loop = asyncio.get_event_loop()
        try:
            out = []
            for prompt in prompts:
                out.append(await loop.run_in_executor(
                    None, _post, server.port, "/v1/generate",
                    {"tokens": [prompt], "max_new_tokens": 12,
                     "temperature": 0, "logprobs": True}))
            out.append(await loop.run_in_executor(
                None, _post, server.port, "/v1/score", {"tokens": prompts[:1]}))
            model = await loop.run_in_executor(
                None, _get, server.port, "/v1/model")
            return out, model["weights"]
        finally:
            await server.stop()

    got, got_weights = run(drive(served), timeout=300)
    want, want_weights = run(drive(master), timeout=300)
    assert got == want
    assert len(got[0]["tokens"][0]) == 12 and got[0]["logprobs"]
    assert want_weights == {"dtype": "float32", "bytes": param_bytes(master)}
    assert got_weights == {
        "dtype": "bfloat16", "bytes": param_bytes(master) // 2}


def test_int8_still_quantizes_from_float32():
    _cfg_, params, _mesh = _load("--int8")
    want = quantize_model_params(init_params(jax.random.PRNGKey(0), _cfg()))
    _assert_same_trees(params, want)
    assert params["layers"]["norm_attn"].dtype == jnp.float32
    assert resident_weights(params)["dtype"] == "int8"


@pytest.mark.parametrize("flags,dtype", [
    ([*MODEL_FLAGS], "bfloat16"),
    ([*MODEL_FLAGS, "--int8"], "int8"),
    (["--model-config", os.path.join(TOY, "toy-axk1.json"),
      "--max-len", "64"], "bfloat16"),
    (["--model-config", os.path.join(TOY, "toy-sdar.json"),
      "--max-len", "64"], "bfloat16"),
], ids=["flagship", "int8", "mla-moe", "block-diffusion"])
def test_resident_weights_names_the_form_of_every_family(flags, dtype):
    _cfg_, params, _mesh = serve_cli.load_model(
        serve_cli.build_arg_parser().parse_args(flags))
    weights = resident_weights(params)
    assert weights == {"dtype": dtype, "bytes": param_bytes(params)}
    assert weights["bytes"] == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def test_tensor_parallel_leaves_keep_their_sharding():
    from containerpilot_tpu.parallel import shard_params

    cfg, params, mesh = _load("--tp", "2")
    assert dict(mesh.shape)["model"] == 2
    want = shard_params(init_params(jax.random.PRNGKey(0), cfg), mesh, cfg)
    for got, ref in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert got.dtype == jnp.bfloat16
        assert got.sharding.is_equivalent_to(ref.sharding, ref.ndim)
    _assert_same_trees(params, _rounded(want))
    assert any(
        not leaf.sharding.is_fully_replicated
        for leaf in jax.tree.leaves(params))


def test_a_lora_merge_is_rounded_after_the_merge(tmp_path):
    """``--lora-dir``: merged in float32, then cast: the cast of the
    merged float32 tree, not a merge into rounded weights."""
    from containerpilot_tpu.models.lora import apply_lora
    from containerpilot_tpu.parallel import (
        MeshPlan,
        make_lora_train_step,
        make_mesh,
        save_checkpoint,
    )

    cfg = _cfg()
    mesh = make_mesh(jax.devices()[:1], plan=MeshPlan(1, 1))
    init_fn, step_fn, _abstract = make_lora_train_step(
        cfg, mesh, rank=4, learning_rate=1e-2)
    state = init_fn(jax.random.PRNGKey(3))
    base = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size, jnp.int32)
    for _ in range(3):
        state, _loss = step_fn(state, base, tokens)
    save_checkpoint(str(tmp_path / "lora"), 3, state)

    _cfg_, params, _mesh = _load(
        "--lora-dir", str(tmp_path / "lora"), "--lora-rank", "4")
    merged = apply_lora(base, state.params, cfg)
    want = _rounded(merged)
    _assert_same_trees(params, want)
    # the adapter moved the weights by more than the rounding hides
    assert not np.array_equal(
        np.asarray(params["layers"]["wq"]),
        np.asarray(base["layers"]["wq"].astype(jnp.bfloat16)))


def test_a_trainers_checkpoint_is_restored_then_rounded(tmp_path):
    from containerpilot_tpu.parallel import (
        MeshPlan,
        init_train_state,
        make_mesh,
        save_checkpoint,
    )

    cfg = _cfg()
    mesh = make_mesh(jax.devices()[:1], plan=MeshPlan(1, 1))
    state = init_train_state(jax.random.PRNGKey(7), cfg, mesh)
    want = _rounded(state.params)
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(state.params))
    save_checkpoint(str(tmp_path / "ck"), 5, state)
    _cfg_, params, _mesh = _load("--checkpoint-dir", str(tmp_path / "ck"))
    _assert_same_trees(params, want)


def test_fetch_params_lands_a_bf16_tree_on_a_bf16_template(run):
    """``--weights-from``: the init tree is the template the fetch lands
    on, so two peers of one build exchange the serving form: half the
    bytes of the float32 tree, the same bits."""
    from containerpilot_tpu.fleet.standby import fetch_params, weights_manifest
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg, served, _mesh = _load()
    # a peer whose weights differ from the template's own
    peer = serving_params(init_params(jax.random.PRNGKey(11), cfg), cfg)
    manifest = weights_manifest(peer)
    assert {leaf["dtype"] for leaf in manifest["leaves"]} == {"bfloat16"}
    assert manifest["total_bytes"] == param_bytes(peer)

    async def scenario():
        server = InferenceServer(
            cfg, peer, "127.0.0.1", 0, max_len=48, slots=2, slot_chunk=4)
        await server.run()
        try:
            return await fetch_params("127.0.0.1", server.port, served)
        finally:
            await server.stop()

    fetched = run(scenario(), timeout=300)
    assert fetched is not None
    _assert_same_trees(fetched, jax.tree.map(np.asarray, peer))
