"""The main path's pallas kernels compile for the v5e, without a chip.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (`jax.experimental.topologies`), so
what it would refuse on the machine with the chip it refuses here, at
no chip time: a slice not aligned to the tiling, more fast memory than
a kernel may use, a program that does not fit 16 GB. These are the
shapes chip_smoke.py's main path runs (the 1.2B-class serving prefill
at 1024 tokens, the flagship training step's attention at batch 8 x
seq 2048, blocks from ops/tuned/tpu-v5-lite.json) plus the windowed
forward and the int8 GEMM at the serving model's FFN width. Nothing
runs, so nothing here says anything about results or times.

The kernels default to interpret mode on the CPU backend the tests
run on, so each compile passes `interpret=False` itself. The
persistent compile cache is switched off around the module: an entry
written for a described chip cannot be read back without one, and the
next run would warn and recompile.
"""
import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from containerpilot_tpu.ops.flash import (
    flash_attention,
    flash_attention_forward,
)
from containerpilot_tpu.ops.quant import int8_matmul_pallas

KERNEL = "tpu_custom_call"
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topology():
    """A described four-chip v5e host, or skip where the topology
    cannot be described (no TPU compiler in the installation)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — any failure = no compiler
        pytest.skip(f"cannot describe a v5e topology here: {exc}")


@pytest.fixture(scope="module")
def chip(topology):
    """One described v5e device."""
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert KERNEL in compiled.as_text(), "the pallas kernel is not in it"
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"
    return compiled


def _qkv(chip, batch, seq, heads, head_dim=128):
    shape = jax.ShapeDtypeStruct(
        (batch, seq, heads, head_dim), jnp.bfloat16, sharding=chip
    )
    return shape, shape, shape


@pytest.mark.parametrize(
    "batch,seq,heads,blocks,window",
    [
        # serving prefill of the 1.2B-class model at the flash crossover
        (1, 1024, 16, (256, 512), 0),
        # the same model at its full context
        (1, 2048, 16, (512, 512), 0),
        # sliding window: kv blocks older than the window are skipped
        (1, 8192, 16, (512, 512), 1024),
    ],
    ids=["prefill-1024", "prefill-2048", "window-1024-of-8192"],
)
def test_flash_forward_compiles(chip, batch, seq, heads, blocks, window):
    fn = functools.partial(
        flash_attention_forward, block_q=blocks[0], block_k=blocks[1],
        interpret=False, window=window,
    )
    _compile(fn, *_qkv(chip, batch, seq, heads))


def test_flash_forward_gqa_compiles(chip):
    """The serving kernel reads grouped kv heads natively."""
    q, _, _ = _qkv(chip, 1, 1024, 16)
    k, v, _ = _qkv(chip, 1, 1024, 4)
    _compile(
        functools.partial(
            flash_attention_forward, block_q=256, block_k=512,
            interpret=False,
        ),
        q, k, v,
    )


def test_flash_backward_compiles(chip):
    """The flagship training step's attention, fwd + bwd through the
    custom_vjp: batch 8, seq 2048, 8 heads, 'train' blocks 512/512."""
    def loss(q, k, v):
        out = flash_attention(
            q, k, v, block_q=512, block_k=512, interpret=False
        )
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), *_qkv(chip, 8, 2048, 8)
    )
    # forward, dq, and dk/dv kernels
    assert compiled.as_text().count(KERNEL) >= 3


def test_int8_gemm_compiles(chip):
    """A padded decode microbatch through the serving model's FFN:
    128 x 2048 . 2048 x 6144, per-column scales."""
    m, k, n = 128, 2048, 6144
    _compile(
        functools.partial(int8_matmul_pallas, interpret=False),
        jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=chip),
        jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=chip),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=chip),
    )


def test_tp_serving_prefill_needs_shard_map(topology, monkeypatch):
    """`serve --tp 4`, a prompt at the flash crossover: Mosaic refuses
    to partition a kernel automatically, so the serving prefill must
    bind its attention through flash_parallel_config (serve_cli's
    load_model does). Interpreted kernels partition like any XLA op,
    which is how the CPU tests never saw this; the test steers the
    model's own interpret default to the chip's."""
    from jax.sharding import NamedSharding

    from containerpilot_tpu.models.decode import prefill
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.ops import flash
    from containerpilot_tpu.parallel import MeshPlan, make_mesh
    from containerpilot_tpu.parallel.context import flash_parallel_config
    from containerpilot_tpu.parallel.sharding import param_sharding_rules

    monkeypatch.setattr(flash, "_resolve_interpret", lambda i: False)
    mesh = make_mesh(
        list(topology.devices), plan=MeshPlan(data=1, model=4)
    )
    # the 1.2B-class serving widths, depth cut to 2 (one scan body)
    cfg = TransformerConfig(
        vocab_size=32768, d_model=2048, n_heads=16, n_layers=2,
        d_ff=6144, max_seq_len=2048, flash_min_seq=1024,
    )
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)
        ),
        shapes, param_sharding_rules(cfg, mesh),
    )
    tokens = jax.ShapeDtypeStruct(
        (1, 1024), jnp.int32,
        sharding=NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )

    def lower(config):
        return jax.jit(
            lambda p, t: prefill(p, t, config, 2048)
        ).lower(params, tokens)

    with pytest.raises(NotImplementedError, match="shard_map"):
        lower(cfg)
    compiled = lower(flash_parallel_config(cfg, mesh)).compile()
    assert KERNEL in compiled.as_text()


def _computations(text):
    """The lines of every computation of an optimised program by its
    name, and the entry computation's name."""
    bodies, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            bodies[name] = []
            entry = name if head.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    return bodies, entry


def _outside_fusions(text):
    """(computation, line) of every instruction of an optimised
    program that is not inside a fused computation: what a fusion
    calls lives inside it and never reaches memory. Also the lines of
    every computation by name."""
    bodies, _entry = _computations(text)
    fused = {
        called for lines in bodies.values() for line in lines
        if " fusion(" in line
        for called in re.findall(r"calls=%?([\w.\-]+)", line)
    }
    outside = [
        (name, line) for name, lines in bodies.items()
        if name not in fused for line in lines
    ]
    return outside, bodies


def _result_types(bodies):
    """The type of every named instruction of an optimised program
    (``_outside_fusions``' bodies), without its layout."""
    types = {}
    for lines in bodies.values():
        for line in lines:
            made = re.match(
                r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])", line)
            if made:
                types[made.group(1)] = made.group(2)
    return types


def _pool_sized_outside_fusions(text, elements, min_rank=0):
    """The instructions outside fused computations that produce a
    tensor of at least ``elements`` elements (in ``min_rank`` or more
    dimensions: the compiler's own prefetches of 2-D weight matrices
    into its fast memory can be as large as a small cache's plane and
    are not the cache), as (name, opcode, type)
    — but for what moves nothing (parameters, tuples and their
    elements, bitcasts, the loops themselves), what only prepares
    WEIGHTS once a dispatch (every operand a ``params`` argument: the
    bf16 copy of a float32 matrix, a matrix's change of layout), and
    the IN-PLACE update: a fusion around a scatter or a
    dynamic-update-slice whose result has its first operand's type,
    that operand being the loop's own carried buffer."""
    outside, bodies = _outside_fusions(text)
    types = _result_types(bodies)
    found = []
    for _computation, line in outside:
        made = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)", line)
        if not made:
            continue
        name, kind, opcode, operands = made.groups()
        if opcode in ("parameter", "get-tuple-element", "tuple", "bitcast",
                      "while", "conditional", "call"):
            continue
        sizes = [
            math.prod(int(n) for n in dims.split(",") if n)
            for dims in re.findall(r"\w+\[([\d,]*)\]", kind)
            if dims.count(",") + 1 >= min_rank
        ]
        if not sizes or max(sizes) < elements:
            continue
        operands = re.findall(r"%([\w.\-]+)", operands)
        if operands and all(o.startswith("params_") for o in operands):
            continue
        if opcode == "fusion" and operands:
            called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
            body = "\n".join(bodies[called])
            first = operands[0]
            if (re.search(r" (scatter|dynamic-update-slice)\(", body)
                    and first.startswith(("get-tuple-element", "pool_"))
                    and types.get(first) == kind.split("{")[0]):
                continue
        found.append((name, opcode, kind.split("{")[0]))
    return found


def test_decode_chunk_keeps_the_cache_as_stored(chip):
    """The slot engine's chunk program at the benchmark's attention
    shapes (Mistral-7B: 32 heads over 8 kv heads of 128, 16 slots x
    4096 positions, bf16; depth, vocabulary and FFN cut, they do not
    touch attention), compiled for the v5e: no instruction of the
    optimised program may produce a tensor as large as a layer's
    cache repeated to 32 heads, nor a float32 one as large as the
    layer's cache. Those two (`broadcast f32[16,4096,8,4,128]`,
    `convert_bitcast_fusion f32[16,4096,8,128]`) were 1.9 of the 2.8
    device seconds of a traced serving window before decode_chunk
    contracted the cache as stored (PERF.md, PR 26); the keys' change
    of layout has to stay inside the contraction's fusion."""
    from containerpilot_tpu.models.slots import (
        _jitted_chunk,
        init_slot_state,
        slot_cache,
    )
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    slots, length = 16, 4096
    cfg = TransformerConfig(
        vocab_size=1024, d_model=4096, n_heads=32, n_kv_heads=8,
        n_layers=2, d_ff=1024, max_seq_len=length,
    )
    shapes = jax.eval_shape(
        lambda: (
            init_params(jax.random.PRNGKey(0), cfg),
            slot_cache(cfg, slots, length),
            init_slot_state(cfg, slots),
        )
    )
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        shapes,
    )
    text = _jitted_chunk(cfg, slots, 8).lower(*shapes).compile().as_text()
    assert text.startswith("HloModule jit_run")
    layer_cache = slots * length * cfg.kv_heads * cfg.head_dim
    found = []
    for name, elem, dims in re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]+)\]", text, re.M
    ):
        dims = [int(n) for n in dims.split(",")]
        if length not in dims or cfg.head_dim not in dims:
            continue
        size = math.prod(dims)
        found.append((name, elem, dims))
        assert size < layer_cache * (cfg.n_heads // cfg.kv_heads), (
            f"{name}: the cache repeated to n_heads, {elem}{dims}"
        )
        assert elem != "f32" or size < layer_cache, (
            f"{name}: a float32 copy of the cache, {dims}"
        )
    assert any(elem == "bf16" for _, elem, _ in found), "no cache found"


def _cell_decode_shapes(chip, slot_cache):
    """The benchmark's mistral-7b-serve cache shapes (16 slots x 4096
    positions x 8 kv heads of 128, bf16, 4 layers; vocabulary and FFN
    cut, they do not touch the cache) as arguments on the described
    chip: (cfg, slots, (params, pool, state), one layer's keys)."""
    from containerpilot_tpu.models.slots import init_slot_state
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    slots, length = 16, 4096
    cfg = TransformerConfig(
        vocab_size=1024, d_model=4096, n_heads=32, n_kv_heads=8,
        n_layers=4, d_ff=1024, max_seq_len=length,
    )
    shapes = jax.eval_shape(
        lambda: (
            init_params(jax.random.PRNGKey(0), cfg),
            slot_cache(cfg, slots, length),
            init_slot_state(cfg, slots),
        )
    )
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        shapes,
    )
    return cfg, slots, shapes, slots * length * cfg.kv_heads * cfg.head_dim


def _weight_converts_outside_fusions(text, params):
    """The ``convert`` instructions outside fused computations whose
    result has as many elements as a leaf of ``params`` (or as one
    layer's slice of a stacked leaf): a program rounding weights it
    was handed in another dtype."""
    counts = set()
    for leaf in jax.tree.leaves(params):
        counts.add(math.prod(leaf.shape))
        counts.add(math.prod(leaf.shape[1:]))
    counts.discard(1)
    outside, _bodies = _outside_fusions(text)
    found = []
    for _computation, line in outside:
        made = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* convert\(",
            line)
        if made and math.prod(
                int(n) for n in made.group(3).split(",") if n) in counts:
            found.append(made.group(1, 2, 3))
    return found


@pytest.mark.parametrize("form", ["float32", "serving"])
@pytest.mark.parametrize("program", ["chunk", "window"])
def test_decode_step_moves_nothing_the_size_of_a_layers_cache(
        chip, program, form):
    """The slot engine's chunk program and its fused window of 4
    rounds at the benchmark's cache shapes, compiled for the v5e:
    outside its fused computations the program produces no tensor with
    as many elements as ONE layer's keys, but for the in-place update
    of the pool's own leaf, and its temporaries stay under one layer's
    keys and values beside the bf16 copy of the (float32) weights.
    With the pool stacked on a leading slot axis and the cache as the
    layer scan's xs/ys, six such operations (two transposes of the
    whole pool, a slice out and a stack back for each of keys and
    values) were 10.7 of a decode step's 17.0 ms on the chip, and the
    temporaries held two copies of the pool (PERF.md, PR 28).

    ``serving``: the tree as ``serve_cli.load_model`` hands it over
    (``serving_params``: every leaf in the compute dtype). The program
    then rounds no weight and holds no copy of one: its temporaries
    stay under one layer's keys and values alone. Handed the float32
    tree it still makes its own bf16 copy at every dispatch (22 % of a
    batch-decode step on the chip, PERF.md, PR 36), and this check
    sees it."""
    from containerpilot_tpu.models.slots import (
        _jitted_chunk,
        _jitted_window,
        slot_cache,
    )

    cfg, slots, shapes, layer_keys = _cell_decode_shapes(chip, slot_cache)
    if form == "serving":
        shapes = (
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, cfg.dtype, sharding=chip),
                shapes[0]),
            *shapes[1:],
        )
    if program == "chunk":
        lowered = _jitted_chunk(cfg, slots, 8).lower(*shapes)
    else:
        budget = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        lowered = _jitted_window(cfg, slots, 8, 4).lower(*shapes, budget)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_run")
    assert _pool_sized_outside_fusions(text, layer_keys) == []
    # the pool is updated where it lies: every byte of it is aliased
    memory = compiled.memory_analysis()
    pool_bytes = 2 * cfg.n_layers * layer_keys * 2
    assert memory.alias_size_in_bytes >= pool_bytes
    converts = _weight_converts_outside_fusions(text, shapes[0])
    if form == "serving":
        assert converts == []
        # no copy of the weights at all: what is left (137 MB here, a
        # change of layout of the attention projections among it)
        # stays under one layer's keys and values with no allowance
        assert memory.temp_size_in_bytes < 2 * layer_keys * 2
    else:
        assert converts, "the float32 tree's bf16 copy went unseen"
        weights_bf16 = 2 * sum(
            math.prod(x.shape) for x in jax.tree.leaves(shapes[0])
        )
        assert memory.temp_size_in_bytes < weights_bf16 + 2 * layer_keys * 2


def test_decode_step_check_sees_the_old_form(chip):
    """The same check on a step built the old way here (one-row caches
    stacked on a leading slot axis, ``decode_step`` vmapped over it
    inside the scan of steps) finds the copies, so the test above
    cannot pass by looking past them."""
    from containerpilot_tpu.models.decode import decode_step, init_cache

    def old_pool(cfg, slots, length):
        row = init_cache(cfg, 1, length)
        return jax.tree.map(
            lambda x: jnp.zeros((slots,) + x.shape, x.dtype), row)

    cfg, slots, (params, pool, state), layer_keys = _cell_decode_shapes(
        chip, old_pool)
    step = jax.vmap(
        lambda params, cache, token: decode_step(params, cache, token, cfg),
        in_axes=(None, 0, 0),
    )

    def run(params, pool, last):
        def body(carry, _):
            pool, tok = carry
            logits, pool = step(params, pool, tok[:, None])
            return (pool, jnp.argmax(logits[:, 0], -1).astype(tok.dtype)), tok
        return jax.lax.scan(body, (pool, last), None, length=8)

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, pool, state["last"]).compile()
    found = _pool_sized_outside_fusions(compiled.as_text(), layer_keys)
    assert len(found) >= 2, found
    weights_bf16 = 2 * sum(
        math.prod(x.shape) for x in jax.tree.leaves(params))
    assert (compiled.memory_analysis().temp_size_in_bytes
            > weights_bf16 + 2 * layer_keys * 2)


def _attention_reads(text, cfg, slots):
    """The types of the keys and values that the attention fusions of
    an optimised decode program take as operands (a fusion outside
    every fused computation, named under the scope ``attn.scores``; an
    operand of slots x N x kv_heads x head_dim): how far a step READS
    each row of the pool."""
    outside, bodies = _outside_fusions(text)
    types = _result_types(bodies)
    rows = re.compile(
        rf"\w+\[{slots},\d+,{cfg.kv_heads},{cfg.head_dim}\]$")
    reads = set()
    for _computation, line in outside:
        made = re.match(
            r"\s*(?:ROOT )?%?[\w.\-]+ = .*? fusion\((.*?)\), kind=", line)
        if not made or "/attn.scores/" not in line:
            continue
        for operand in re.findall(r"%([\w.\-]+)", made.group(1)):
            if rows.match(types.get(operand, "")):
                reads.add(types[operand])
    return reads


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_a_cut_read_reads_the_pools_rows_to_read_len(chip, program):
    """The decode programs at ``read_len`` 1,024 of 4,096, at the
    benchmark's cache shapes, compiled for the v5e: attention's
    fusions take each layer's keys and values as
    ``bf16[16,1024,8,128]``, a quarter of the leaf; outside its fused
    computations the program still produces nothing with as many
    elements as a layer's WHOLE leaf but the in-place update, and the
    largest thing it does produce there is a cut leaf (the compiler
    makes the cut a ``slice`` of its own and does not fuse it into the
    contraction: PERF.md, PR 41); the pool is aliased whole (writes go
    to the whole leaf where it lies) and the temporaries stay under
    one layer's keys and values, as for the whole-row program."""
    from containerpilot_tpu.models.slots import (
        _jitted_chunk,
        _jitted_window,
        slot_cache,
    )

    read_len = 1024
    cfg, slots, shapes, layer_keys = _cell_decode_shapes(chip, slot_cache)
    shapes = (
        jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, cfg.dtype, sharding=chip),
            shapes[0]),
        *shapes[1:],
    )
    if program == "chunk":
        lowered = _jitted_chunk(cfg, slots, 8, None, read_len).lower(*shapes)
    else:
        budget = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        lowered = _jitted_window(
            cfg, slots, 8, 4, None, read_len).lower(*shapes, budget)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_run")
    cut = f"bf16[{slots},{read_len},{cfg.kv_heads},{cfg.head_dim}]"
    assert _attention_reads(text, cfg, slots) == {cut}
    assert _pool_sized_outside_fusions(text, layer_keys) == []
    cut_leaf = layer_keys * read_len // cfg.max_seq_len
    # (a stacked weight's change of layout, once a dispatch, has as
    # many elements at these widths: ``bf16[4,4096,8,128]``)
    assert {kind for _name, _opcode, kind in _pool_sized_outside_fusions(
        text, cut_leaf) if f"[{slots}," in kind} <= {cut}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * cfg.n_layers * layer_keys * 2
    assert memory.temp_size_in_bytes < 2 * layer_keys * 2


def test_read_length_check_sees_a_whole_leaf_read(chip):
    """The same reading of the whole-row chunk program (no
    ``read_len``: the pod's, and every rung-less pool's) finds the
    whole leaf at attention's fusions, so the test above cannot pass
    by looking past the read."""
    from containerpilot_tpu.models.slots import _jitted_chunk, slot_cache

    cfg, slots, shapes, _layer_keys = _cell_decode_shapes(chip, slot_cache)
    text = _jitted_chunk(cfg, slots, 8).lower(*shapes).compile().as_text()
    assert _attention_reads(text, cfg, slots) == {
        f"bf16[{slots},{cfg.max_seq_len},{cfg.kv_heads},{cfg.head_dim}]"}


def _expert_kernels(text):
    """(calls of the experts' grouped kernel under ``mlp.experts``, the
    matrix products under that scope that are NOT the kernel) in an
    optimised program: the loop that ran one block of one expert a turn
    (a ``while`` whose body held the three ``dot``s) left products
    there, the kernel leaves none."""
    calls, products = [], []
    for line in text.splitlines():
        if "/mlp.experts/" not in line:
            continue
        if " custom-call(" in line and KERNEL in line:
            assert "moe_grouped_matmul" in line, line[:200]
            calls.append(line)
        elif re.search(r" (dot|convolution|while)\(", line) or re.search(
                r"/mlp\.experts/[^\"]*dot_general", line):
            products.append(line[:200])
    return calls, products


@pytest.mark.parametrize("cell,rows,k,d,f,held,experts", [
    ("sdar-30b-a3b-serve.block-decode", 256, 8, 2048, 768, 128, 128),
    ("granite-4-h-small-serve.ssm-decode", 64, 10, 4096, 768, 36, 72),
    ("ax-k1-serve.ep-decode", 64, 8, 7168, 2048, 12, 192),
    # the same cells' 1,536-token prefills: tiles of 128 rows, and the
    # tokens' float32 sums kept in fast memory beside the weights
    ("sdar-30b-a3b-serve prefill", 1536, 8, 2048, 768, 128, 128),
    ("granite-4-h-small-serve prefill", 1536, 10, 4096, 768, 36, 72),
    ("ax-k1-serve prefill", 1536, 8, 7168, 2048, 12, 192),
])
def test_grouped_experts_kernel_compiles(
        chip, monkeypatch, cell, rows, k, d, f, held, experts):
    """``moe.sparse_experts`` at the three expert cells' published
    shapes (a decode step's rows and a long prompt's), bfloat16,
    compiled for the v5e by Mosaic (the model's own interpret default is
    steered to the chip's): ONE custom call, the grouped kernel, and no
    ``while`` left of the loop it replaces."""
    from containerpilot_tpu.models import moe
    from containerpilot_tpu.ops import flash

    monkeypatch.setattr(flash, "_resolve_interpret", lambda i: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    compiled = _compile(
        lambda h, idx, gate, w_gate, w_up, w_down: moe.sparse_experts(
            h, idx, gate, w_gate, w_up, w_down, 0, experts),
        shape((rows, d)), shape((rows, k), jnp.int32),
        shape((rows, k), jnp.float32), shape((held, d, f)),
        shape((held, d, f)), shape((held, f, d)))
    text = compiled.as_text()
    calls, products = _expert_kernels(text)
    assert len(calls) == 1 and products == []
    assert " while(" not in text


def test_latent_expert_step_fits_the_chip_and_copies_no_weights(
        chip, monkeypatch):
    """The slot engine's chunk program of the benchmark's A.X-K1
    configuration at its real size (benchmark/configs/ax-k1-serve.json:
    published widths, 12 held experts of 192, six layers, 64 slots x
    3,072 positions), compiled for the v5e: weights, pool and
    temporaries fit the chip, and outside its fused computations the
    program produces nothing as large as one layer's held experts or
    one dense matrix. A scan over stacked per-layer leaves did (the
    compiler copied each layer's 1.06 GB of experts out of the stack on
    every step, PERF.md PR 27), which is why the family's layers are
    separate leaves, unrolled. Each sparse layer's routed experts are
    ONE call of the grouped kernel (ops/moe_grouped_matmul.py, compiled
    by Mosaic), and no matrix product is left under ``mlp.experts``
    beside it."""
    from containerpilot_tpu.models.slots import (
        _jitted_chunk,
        init_slot_state,
        slot_cache,
    )
    from containerpilot_tpu.ops import flash
    from containerpilot_tpu.workload.modelcfg import load_model_file

    monkeypatch.setattr(flash, "_resolve_interpret", lambda i: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    slots, length = 64, 3072
    cfg = load_model_file(
        os.path.join(root, "benchmark", "configs", "ax-k1-serve.json"),
        length)
    shapes = jax.eval_shape(
        lambda: (
            cfg.family.init_params(None, cfg),
            slot_cache(cfg, slots, length),
            init_slot_state(cfg, slots),
        )
    )
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        shapes,
    )
    # (built anew: a cached program holds the kernel of its first build)
    compiled = _jitted_chunk.__wrapped__(cfg, slots, 8).lower(
        *shapes).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert 8.3e9 < memory.argument_size_in_bytes < 10.5e9
    assert held < 0.8 * HBM_BYTES
    text = compiled.as_text()
    calls, products = _expert_kernels(text)
    assert len(calls) == cfg.n_layers - cfg.first_dense and products == []
    outside, _bodies = _outside_fusions(text)
    one_expert_matrix = cfg.d_model * cfg.moe_d_ff
    for _computation, line in outside:
        found = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = bf16\[([\d,]+)\]\S* "
            r"(copy|fusion|dynamic-slice|transpose)\(", line)
        if not found or "scatter" in line:
            continue  # the latents' in-place write is a scatter fusion
        size = math.prod(int(n) for n in found.group(2).split(","))
        assert size < 4 * one_expert_matrix, (
            f"{found.group(1)}: {found.group(3)} of bf16"
            f"[{found.group(2)}] outside a fusion"
        )


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_block_diffusion_pool_forward_fits_the_chip(
        chip, program, monkeypatch):
    """The block-diffusion step program's two programs at the
    benchmark's real size (benchmark/configs/sdar-30b-a3b-serve.json:
    published widths, all 128 experts of six layers, the whole
    vocabulary, 64 slots x 3,072 positions, 6 pool forwards a round),
    compiled for the v5e: weights (8.72 GB), pool (2.42 GB) and
    temporaries fit the chip, the pool is updated in place (aliased),
    and outside its fused computations nothing as large as a layer's
    keys is copied or transposed. Each layer's routed experts are ONE
    call of the grouped kernel (ops/moe_grouped_matmul.py, compiled by
    Mosaic), and no matrix product is left under ``mlp.experts`` beside
    it."""
    from containerpilot_tpu.models import block_diffusion as bd
    from containerpilot_tpu.ops import flash
    from containerpilot_tpu.workload.modelcfg import load_model_file

    monkeypatch.setattr(flash, "_resolve_interpret", lambda i: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    slots, length = 64, 3072
    cfg = load_model_file(
        os.path.join(root, "benchmark", "configs", "sdar-30b-a3b-serve.json"),
        length)
    size = cfg.block_length
    shapes = jax.eval_shape(
        lambda: (
            bd.init_params(None, cfg), bd.slot_cache(cfg, slots, length),
            {"blk": jnp.zeros((slots, size), jnp.int32),
             "hidden": jnp.ones((slots, size), jnp.bool_),
             "step": jnp.zeros((slots,), jnp.int32),
             "done": jnp.ones((slots,), jnp.bool_)},
            jnp.zeros((slots,), jnp.int32),
        )
    )
    params, pool, state, budget = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        shapes,
    )
    # (built anew: a cached program holds the kernel of its first build)
    if program == "chunk":
        compiled = bd._jitted_chunk.__wrapped__(cfg, slots, 6).lower(
            params, pool, state).compile()
    else:
        compiled = bd._jitted_window.__wrapped__(cfg, slots, 6, 4).lower(
            params, pool, state, budget).compile()
    text = compiled.as_text()
    calls, products = _expert_kernels(text)
    assert len(calls) == cfg.n_layers and products == []
    # the kernel asks for little more fast memory than it fills
    # (ops/moe_grouped_matmul.py MARGIN), so that the compiler still
    # keeps every layer's attention scores (100 MB) there: with 16 MiB
    # more asked for they fell out, 2.5 ms a forward (PERF.md, PR 47)
    groups = cfg.n_heads // cfg.n_kv_heads
    scores = (f"f32[{slots},{cfg.n_kv_heads},{groups},{size},{length}]"
              "{4,2,3,1,0:T(8,128)S(1)}")
    assert text.count(f"{scores}) fusion(") == cfg.n_layers
    memory = compiled.memory_analysis()
    layer_keys = slots * length * cfg.n_kv_heads * cfg.head_dim
    assert 11.0e9 < memory.argument_size_in_bytes < 11.3e9
    assert memory.alias_size_in_bytes >= 2 * cfg.n_layers * layer_keys * 2
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert held < 0.8 * HBM_BYTES
    outside, _bodies = _outside_fusions(text)
    for _computation, line in outside:
        found = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = bf16\[([\d,]+)\]\S* "
            r"(copy|dynamic-slice|transpose)\(", line)
        if found:
            moved = math.prod(int(n) for n in found.group(2).split(","))
            assert moved < layer_keys, (
                f"{found.group(1)}: {found.group(3)} of bf16"
                f"[{found.group(2)}] outside a fusion")


def _hybrid_ssm_shapes(chip, slots, length):
    from containerpilot_tpu.models.slots import init_slot_state, slot_cache
    from containerpilot_tpu.workload.modelcfg import load_model_file

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_model_file(
        os.path.join(root, "benchmark", "configs",
                     "granite-4-h-small-serve.json"), length)
    shapes = jax.eval_shape(
        lambda: (
            cfg.family.init_params(None, cfg),
            slot_cache(cfg, slots, length),
            init_slot_state(cfg, slots),
            cfg.family.init_cache(cfg, 1, length),
        )
    )
    return cfg, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        shapes,
    )


@pytest.fixture(scope="module")
def hybrid_chunk(chip):
    """(cfg, compiled): the granite configuration's chunk program at
    its real size, compiled once for the tests that read it."""
    from containerpilot_tpu.models.slots import _jitted_chunk
    from containerpilot_tpu.ops import flash

    slots, length = 64, 3072
    cfg, (params, pool, state, _row) = _hybrid_ssm_shapes(chip, slots, length)
    # the experts' kernel as Mosaic compiles it, not interpreted (built
    # anew: a cached program holds the kernel of its first build)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(flash, "_resolve_interpret", lambda i: False)
        return cfg, _jitted_chunk.__wrapped__(cfg, slots, 8).lower(
            params, pool, state).compile()


def test_hybrid_state_space_step_updates_the_state_where_it_lies(
        hybrid_chunk):
    """The slot engine's chunk program of the benchmark's
    granite-4.0-h-small configuration at its real size
    (benchmark/configs/granite-4-h-small-serve.json: published widths,
    nine mamba layers and one attention layer, 36 held experts of 72,
    64 slots x 3,072 positions), compiled for the v5e: weights (9.51
    GB), pool (3.26 GB: 2.44 of recurrent state, 0.81 of keys and
    values) and temporaries fit the chip; the whole pool is aliased;
    each mamba layer's state is read by ONE fused computation a step,
    which gives the new state and the read through C together; and
    outside fused computations nothing of a state's size is produced
    and no weight as large as four expert matrices is copied, but the
    attention layer's query projection's change of layout, once a
    dispatch. Each layer's routed experts are ONE call of the grouped
    kernel (ops/moe_grouped_matmul.py), and no matrix product is left
    under ``mlp.experts`` beside it."""
    slots, length = 64, 3072
    cfg, compiled = hybrid_chunk
    memory = compiled.memory_analysis()
    state_elements = slots * cfg.d_inner * cfg.ssm_state
    pool_bytes = (cfg.n_mamba * state_elements * 4
                  + 2 * slots * length * cfg.n_kv_heads * cfg.head_dim * 2)
    assert 12.7e9 < memory.argument_size_in_bytes < 12.9e9
    assert memory.alias_size_in_bytes >= pool_bytes
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert held < 0.85 * HBM_BYTES
    assert memory.temp_size_in_bytes < state_elements * 4
    text = compiled.as_text()
    outside, bodies = _outside_fusions(text)
    state_type = f"f32[{slots},{cfg.ssm_heads},{cfg.ssm_head_dim},{cfg.ssm_state}]"
    updates = 0
    for _computation, line in outside:
        made = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)", line)
        if not made:
            continue
        name, kind, opcode, operands = made.groups()
        if state_type in kind and opcode not in (
                "parameter", "get-tuple-element", "tuple", "bitcast",
                "while", "conditional", "call"):
            # the update: the state in, (the read through C, the state) out
            assert opcode == "fusion" and kind.startswith("("), line[:200]
            assert kind.count(state_type) == 1
            updates += 1
        found = re.match(r"bf16\[([\d,]+)\]", kind)
        if found and opcode in ("copy", "dynamic-slice", "transpose"):
            moved = math.prod(int(n) for n in found.group(1).split(","))
            assert (moved < 4 * cfg.d_model * cfg.moe_d_ff
                    or "wq" in operands), line[:200]
    assert updates == cfg.n_mamba
    calls, products = _expert_kernels(text)
    assert len(calls) == cfg.n_layers and products == []


def test_hybrid_state_space_insert_overwrites_a_row_in_place(chip):
    """The insert program at the same size: a prefilled row (37.7 MB of
    state, 12.6 MB of keys and values) is written into the donated
    pool, which is aliased whole; nothing else is held."""
    from containerpilot_tpu.models.slots import _jitted_insert

    slots, length = 64, 3072
    cfg, (_params, pool, _state, row) = _hybrid_ssm_shapes(chip, slots, length)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    compiled = _jitted_insert(cfg).lower(pool, row, slot).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes > 3.2e9
    assert memory.temp_size_in_bytes < 64 * 1024 ** 2
    assert (memory.output_size_in_bytes - memory.alias_size_in_bytes
            < 1024 ** 2)


@pytest.mark.parametrize("which", ["flagship", "hybrid-ssm"])
def test_admit_row_writes_the_row_and_the_state_where_they_lie(chip, which):
    """An admission's ONE program (models/slots.py ``admit_row``: row
    key, first sample, the row's write, the state's write) at the
    benchmark's pool shapes (Mistral: 16 slots x 4,096 positions;
    granite: 64 x 3,072 with its recurrent state, through the family's
    ``insert_row``), compiled for the v5e: pool and state are donated
    and aliased whole, the only new output is the first token, next to
    nothing is held besides, and outside fused computations no
    instruction produces a tensor as large as the pool's smallest big
    leaf but the in-place writes of the row (a fusion around a
    dynamic-update-slice over the pool's own parameters)."""
    from containerpilot_tpu.models.decode import _jitted_prefill
    from containerpilot_tpu.models.slots import (
        ADMIT_ROW_WIDTH,
        _jitted_admit_row,
        slot_cache,
    )

    if which == "flagship":
        cfg, _slots, (params, pool, state), _keys = _cell_decode_shapes(
            chip, slot_cache)
        length = 4096
    else:
        length = 3072
        cfg, (params, pool, state, _row) = _hybrid_ssm_shapes(
            chip, 64, length)
    prompt = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=chip)
    logits, row = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(_jitted_prefill(cfg, length), params, prompt))
    packed = jax.ShapeDtypeStruct(
        (ADMIT_ROW_WIDTH,), jnp.int32, sharding=chip)
    compiled = _jitted_admit_row(cfg).lower(
        pool, state, logits, row, packed).compile()
    donated = sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves((pool, state)))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= donated
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 4096
    assert memory.temp_size_in_bytes < 64 * 1024 ** 2
    big = min(
        size for size in (math.prod(leaf.shape)
                          for leaf in jax.tree.leaves(pool))
        if size >= 2 ** 24)
    outside, bodies = _outside_fusions(compiled.as_text())
    writes = 0
    for _computation, line in outside:
        made = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)", line)
        if not made:
            continue
        _name, kind, opcode, operands = made.groups()
        if opcode in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            continue
        sizes = [math.prod(int(n) for n in dims.split(",") if n)
                 for dims in re.findall(r"\w+\[([\d,]*)\]", kind)]
        if not sizes or max(sizes) < big:
            continue
        assert opcode == "fusion", line[:200]
        called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
        assert " dynamic-update-slice(" in "\n".join(bodies[called]), line[:200]
        assert "%pool_" in operands, line[:200]
        writes += 1
    assert writes >= 1


def _vocabulary_sorts(text, vocab):
    """The ``sort`` instructions of an optimised program over a
    dimension of ``vocab``, as (inside, outside): whether the
    computation that holds one is reached from the entry ONLY through
    a ``conditional``'s branch (then it runs when the branch is
    taken), or also by calls, fusions and loops alone (then it runs
    every time)."""
    bodies, entry = _computations(text)
    always, queue = {entry}, [entry]
    while queue:
        for line in bodies[queue.pop()]:
            # every computation a line names but a conditional's arms
            line = re.sub(
                r"(branch_computations=\{[^}]*\}"
                r"|(true|false)_computation=%?[\w.\-]+)", "", line)
            for called in re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
                if called not in always:
                    always.add(called)
                    queue.append(called)
    inside, outside = [], []
    for name, lines in bodies.items():
        for line in lines:
            made = re.match(
                r"\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+\[[\d,]*\])\S* .*? sort\(",
                line)
            if made and re.search(rf"[\[,]{vocab}[\],]", made.group(2)):
                (outside if name in always else inside).append(
                    (name, made.group(1), made.group(2)))
    return inside, outside


@pytest.mark.parametrize("program", ["chunk", "window", "hybrid-ssm chunk"])
def test_the_vocabulary_is_sorted_only_inside_a_conditionals_branch(
        chip, program, request):
    """The sampler's sort of the whole vocabulary (the largest single
    device operation of four serving cells while every request was
    greedy: PERF.md, PR 37) stands in a branch computation of a
    ``conditional`` in the program the v5e's compiler makes, not in
    the step loop's body: a pool whose live rows are all greedy does
    not run it. The flagship's chunk and fused-window programs at cut
    widths, and the granite configuration's chunk program at its real
    size (a file-described family through the same
    ``_round_step_body``)."""
    from containerpilot_tpu.models.slots import (
        _jitted_chunk,
        _jitted_window,
        slot_cache,
    )

    if program == "hybrid-ssm chunk":
        cfg, compiled = request.getfixturevalue("hybrid_chunk")
    else:
        cfg, slots, shapes, _keys = _cell_decode_shapes(chip, slot_cache)
        if program == "chunk":
            lowered = _jitted_chunk(cfg, slots, 8).lower(*shapes)
        else:
            budget = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
            lowered = _jitted_window(cfg, slots, 8, 4).lower(*shapes, budget)
        compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) >= 1
    inside, outside = _vocabulary_sorts(text, cfg.vocab_size)
    assert outside == []
    assert inside, "no sort of the vocabulary found at all"


def test_vocabulary_sort_check_sees_the_old_form(chip):
    """The same check on the sampler as it was (sort, masks and draw
    for every row at every step, then the argmax for the greedy rows)
    inside a loop of steps finds the sort outside any branch, so the
    test above cannot pass by looking past it."""
    from containerpilot_tpu.models.decode import NEG_INF

    rows, vocab = 16, 1024

    def old_sample(logits, keys, t, top_k, top_p):
        x = logits / jnp.maximum(t[:, None], 1e-6)
        ordered = jnp.sort(x, axis=-1)[:, ::-1]
        k = jnp.where(top_k > 0, top_k, vocab)[:, None]
        keep = jnp.arange(vocab)[None, :] < k
        p = jnp.where((top_p > 0.0) & (top_p < 1.0), top_p, 1.0)[:, None]
        probs = jax.nn.softmax(ordered, axis=-1)
        keep &= (jnp.cumsum(probs, axis=-1) - probs) < p
        threshold = jnp.min(
            jnp.where(keep, ordered, jnp.inf), axis=-1, keepdims=True)
        x = jnp.where(x < threshold, NEG_INF, x)
        drawn = jax.vmap(jax.random.categorical)(keys, x)
        return jnp.where(t <= 0.0, jnp.argmax(logits, axis=-1), drawn)

    def run(logits, keys, t, top_k, top_p):
        def body(tok, idx):
            folded = jax.vmap(jax.random.fold_in)(keys, idx + tok)
            return old_sample(
                logits + tok[:, None], folded, t, top_k, top_p), tok
        return jax.lax.scan(
            body, jnp.zeros((rows,), jnp.int32), jnp.arange(8))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    text = jax.jit(run).lower(
        shape((rows, vocab), jnp.float32), shape((rows, 2), jnp.uint32),
        shape((rows,), jnp.float32), shape((rows,), jnp.int32),
        shape((rows,), jnp.float32),
    ).compile().as_text()
    inside, outside = _vocabulary_sorts(text, vocab)
    assert inside == [] and outside, (inside, outside)


# -- the looped family at its published size (PR 42) ----------------------

GIB = 1024 ** 3
#: what the chip's compiler allows a program (PERF.md, PR 42)
LOOPED_ROOM = 15.75 * GIB


def _looped_shapes(chip, slots, length):
    from containerpilot_tpu.models.slots import init_slot_state, slot_cache
    from containerpilot_tpu.workload.modelcfg import load_model_file

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_model_file(
        os.path.join(root, "benchmark", "configs", "ouro-2.6b-serve.json"),
        length)
    shapes = jax.eval_shape(
        lambda: (
            cfg.family.init_params(None, cfg),
            slot_cache(cfg, slots, length),
            init_slot_state(cfg, slots),
            cfg.family.init_cache(cfg, 1, length),
        )
    )
    return cfg, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        shapes,
    )


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_looped_step_reads_each_passes_plane_where_it_lies(chip, program):
    """The slot engine's decode programs of the benchmark's Ouro-2.6B
    configuration at its PUBLISHED size (benchmark/configs/
    ouro-2.6b-serve.json: 48 layers run 4 times, nothing reduced; 16
    slots x 320 positions), compiled for the v5e: weights (4.97 GiB)
    and pool (7.50 GiB: 192 planes of 16 x 320 positions) are the
    arguments, the WHOLE pool is aliased to its output (no second
    pool), arguments and temporaries fit under the 15.75 GiB the
    chip's compiler allows with a gibibyte to spare for a row in
    flight; every matrix is bfloat16 (no float32 copy of the model);
    the program holds 48 layer bodies, not 192: each layer's two
    leaves ``[4, 16, 320, 16, 128]`` are written by ONE scatter each
    and read through a ``dynamic-slice`` at the pass loop's index that
    lives INSIDE the fusion that contracts it, so that outside fused
    computations nothing the size of a plane is produced but the
    in-place writes, and no weight is copied into another layout."""
    from containerpilot_tpu.models.slots import _jitted_chunk, _jitted_window

    slots, length = 16, 320
    cfg, (params, pool, state, _row) = _looped_shapes(chip, slots, length)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree.leaves(params) if x.ndim >= 2)
    if program == "chunk":
        lowered = _jitted_chunk(cfg, slots, 8).lower(params, pool, state)
    else:
        budget = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        lowered = _jitted_window(cfg, slots, 8, 4).lower(
            params, pool, state, budget)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    plane = slots * length * cfg.n_kv_heads * cfg.head_dim
    pool_bytes = cfg.cache_planes * 2 * plane * 2
    assert pool_bytes == slots * length * 1_572_864 == 7.5 * GIB
    assert 12.4 * GIB < memory.argument_size_in_bytes < 12.5 * GIB
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 1024 ** 2
    assert memory.temp_size_in_bytes < 0.5 * GIB
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < LOOPED_ROOM - GIB
    text = compiled.as_text()
    leaf = f"bf16[{cfg.passes},{slots},{length},{cfg.n_kv_heads},{cfg.head_dim}]"
    _outside, bodies = _outside_fusions(text)
    scatters = sum(
        1 for lines in bodies.values() for line in lines
        if " scatter(" in line and f"= {leaf}" in line)
    assert scatters == 2 * cfg.n_layers  # one a leaf: 48 bodies, not 192
    reads = [
        line for lines in bodies.values() for line in lines
        if " dynamic-slice(" in line and leaf.replace(
            f"[{cfg.passes},", "[1,") in line]
    assert len(reads) >= 2 * cfg.n_layers
    assert _pool_sized_outside_fusions(text, plane, min_rank=4) == []
    # the scopes a device trace splits a step by, in the operations' paths
    assert "loop.pass/layers/attn/attn.scores" in text
    assert "loop.pass/layers/mlp" in text and "loop.norm_out/norm" in text
    weight = cfg.d_model * cfg.n_heads * cfg.head_dim
    copies = [
        line for _c, line in _outside_fusions(text)[0]
        if re.search(r" copy\(", line)
        and any(math.prod(int(n) for n in dims.split(",") if n) >= weight
                for dims in re.findall(r"bf16\[([\d,]*)\]", line.split("=")[1]))]
    assert copies == []


def test_looped_check_sees_a_plane_copied_out(chip):
    """The same check on a step that slices the plane out of its leaf
    BEFORE a contraction that wants another layout (the stacked cache's
    fault: PERF.md, PR 28) finds the copy, so the test above cannot
    pass by looking past it."""
    leaf = jax.ShapeDtypeStruct((4, 16, 320, 16, 128), jnp.bfloat16,
                                sharding=chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def copied(keys, t):
        plane = jax.lax.dynamic_index_in_dim(keys, t, 0, keepdims=False)
        return jnp.transpose(plane, (2, 0, 1, 3)) * 2

    text = jax.jit(copied).lower(leaf, t).compile().as_text()
    assert _pool_sized_outside_fusions(
        text, 16 * 320 * 16 * 128, min_rank=4)


def test_looped_insert_and_prefill_fit_beside_the_pool(chip):
    """The other two programs an admission runs at the published size:
    the insert writes a prefilled row's 192 planes (0.47 GiB) into the
    donated pool, which is aliased whole; a 128-token prefill holds the
    weights, its row and under half a gibibyte of temporaries, so that
    weights + pool + a row in flight + either program's temporaries
    stay under what the compiler allows."""
    from containerpilot_tpu.models.decode import prefill
    from containerpilot_tpu.models.slots import _jitted_insert

    slots, length = 16, 320
    cfg, (params, pool, _state, row) = _looped_shapes(chip, slots, length)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    memory = _jitted_insert(cfg).lower(pool, row, slot).compile().memory_analysis()
    assert memory.alias_size_in_bytes >= 7.5 * GIB
    assert memory.temp_size_in_bytes < 64 * 1024 ** 2
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 1024 ** 2
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=chip)
    memory = jax.jit(lambda p, t: prefill(p, t, cfg, length)).lower(
        params, tokens).compile().memory_analysis()
    row_bytes = length * 1_572_864
    assert row_bytes <= memory.output_size_in_bytes < row_bytes + 1024 ** 2
    assert memory.temp_size_in_bytes < 0.5 * GIB
    weights = memory.argument_size_in_bytes
    assert 4.9 * GIB < weights < 5.0 * GIB
    assert (weights + 7.5 * GIB + row_bytes + memory.temp_size_in_bytes
            ) < LOOPED_ROOM - GIB


# -- the decoder-hybrid-decoder family at its published size -----------------


def _decoder_hybrid_shapes(chip, slots, length):
    from containerpilot_tpu.models.slots import init_slot_state, slot_cache
    from containerpilot_tpu.workload.modelcfg import load_model_file

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_model_file(
        os.path.join(root, "benchmark", "configs",
                     "phi-4-mini-flash-serve.json"), length)
    shapes = jax.eval_shape(
        lambda: (
            cfg.family.init_params(None, cfg),
            slot_cache(cfg, slots, length),
            init_slot_state(cfg, slots),
        )
    )
    return cfg, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        shapes,
    )


#: a row of the cell's pool: nine states and tails, eight rings of 512
#: positions, one plane of 3,072 (ISSUE 45's arithmetic)
DECODER_HYBRID_ROW = 3_225_600 + 20_971_520 + 15_728_640


def _plane_contractions(text, plane):
    """The fusions of an optimised program, outside every fused
    computation and named under ``attn.full`` or ``attn.cross``, that
    take an operand of the type ``plane`` (the WHOLE leaf of keys or of
    values): the plain contraction over every position of every row."""
    outside, bodies = _outside_fusions(text)
    types = _result_types(bodies)
    found = []
    for _computation, line in outside:
        made = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = .*? fusion\((.*?)\), kind=", line)
        if not made or not re.search(r"/attn\.(cross|full)/", line):
            continue
        if any(types.get(operand) == plane
               for operand in re.findall(r"%([\w.\-]+)", made.group(2))):
            found.append(made.group(1))
    return found


def _plane_kernels(text):
    """The per-row kernel's calls in an optimised program, by the scope
    they stand under."""
    return [
        re.search(r"/(attn\.\w+)/", line).group(1)
        for line in text.splitlines()
        if " custom-call(" in line and KERNEL in line
        and "ragged_decode_attention" in line]


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_decoder_hybrid_step_writes_and_reads_its_caches_where_they_lie(
        chip, program, monkeypatch):
    """The slot engine's decode programs of the benchmark's
    Phi-4-mini-flash configuration at its PUBLISHED size
    (benchmark/configs/phi-4-mini-flash-serve.json: 32 layers, nothing
    reduced; 64 slots x 3,072 positions), compiled for the v5e: weights
    (7.705 GB) and pool (64 rows of 39.9 MB) are the arguments, the
    WHOLE pool is aliased to its output, temporaries stay under half a
    gibibyte and everything under 0.8 of the chip; every matrix is
    bfloat16 and every recurrent state float32; each of the eight rings
    and the ONE plane is written by a scatter over the leaf seen as
    [rows x pairs, length, 128] (in place), and outside fused
    computations nothing the size of a ring is copied, transposed or
    sliced out: the seven cross layers read layer 17's plane where it
    lies, no copy a layer, none a step. The plane's eight reads a step
    are the per-row kernel (ops/ragged_decode.py, compiled by Mosaic:
    the model's own interpret default is steered to the chip's), one
    under ``attn.full`` and seven under ``attn.cross``, and no fusion
    under those scopes takes the whole plane any more. The family's
    option for the compiler (none of its asynchronous evictions) is
    steered on as the kernel is: the CPU backend the tests run on does
    not take it."""
    from containerpilot_tpu.models import decoder_hybrid as dh
    from containerpilot_tpu.models.slots import _jitted_chunk, _jitted_window
    from containerpilot_tpu.ops import flash

    monkeypatch.setattr(flash, "_resolve_interpret", lambda i: False)
    monkeypatch.setattr(dh, "decode_compiler_options",
                        lambda: dh.DECODE_COMPILER_OPTIONS)
    slots, length = 64, 3072
    cfg, (params, pool, state) = _decoder_hybrid_shapes(chip, slots, length)
    assert params["embed"].dtype == jnp.bfloat16
    assert all(x.dtype == jnp.bfloat16
               for layer in params["layers"] for name, x in layer.items()
               if name.startswith(("w_", "g_", "b_", "conv_")))
    assert all(x.dtype == jnp.float32 and x.shape == (slots, 16, 5120)
               for x in pool["ssm"])
    assert len(pool["k"]) == 1 and len(pool["ring_k"]) == 8
    # (built anew: a cached program holds the options of its first build)
    if program == "chunk":
        lowered = _jitted_chunk.__wrapped__(cfg, slots, 8).lower(
            params, pool, state)
    else:
        budget = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        lowered = _jitted_window.__wrapped__(cfg, slots, 8, 4).lower(
            params, pool, state, budget)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    pool_bytes = slots * DECODER_HYBRID_ROW
    assert 10.2e9 < memory.argument_size_in_bytes < 10.4e9
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 0.5 * GIB
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < 0.8 * HBM_BYTES
    text = compiled.as_text()
    _outside, bodies = _outside_fusions(text)
    pairs, width = cfg.kv_pairs, cfg.pair_dim
    # every Mamba state is written where it lies by the ONE fusion that
    # computes it, none copied back out of the compiler's fast memory
    # behind later operations (``decode_compiler_options``)
    state = rf"f32\[{slots},16,5120\]"
    written = re.findall(
        rf"= \(f32\[{slots},5120\]\S* {state}(\S*)\) fusion\(", text)
    assert len(written) == 9 and not any("S(1)" in to for to in written)
    assert not re.search(rf"= \({state}\S* {state}\S* \S+ copy-start\(", text)
    kernels = _plane_kernels(text)
    assert sorted(set(kernels)) == ["attn.cross", "attn.full"]
    assert kernels.count("attn.cross") == 7 * kernels.count("attn.full")
    assert _plane_contractions(
        text, f"bf16[{slots},{pairs},{length},{width}]") == []
    for rows, count in ((512, 2 * 8), (length, 2)):
        leaf = f"bf16[{slots * pairs},{rows},{width}]"
        scatters = sum(
            1 for lines in bodies.values() for line in lines
            if " scatter(" in line and f"= {leaf}" in line)
        assert scatters == count, leaf
    # (the compiler's own prefetches of a ring into its fast memory,
    # ``slice-start`` / ``copy-start`` to memory space 1, are not copies)
    ring = slots * pairs * 512 * width
    for _computation, line in _outside:
        found = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = bf16\[([\d,]+)\]\S* "
            r"(copy|dynamic-slice|transpose)\(", line)
        if found:
            moved = math.prod(int(n) for n in found.group(2).split(","))
            assert moved < ring, (
                f"{found.group(1)}: {found.group(3)} of bf16"
                f"[{found.group(2)}] outside a fusion")
    # the scopes a device trace splits a step by, in the operations' paths
    for scope in ("layers/attn/attn.window", "layers/attn/attn.full",
                  "layers/attn/attn.cross", "layers/attn/attn.diff",
                  "layers/ssm/ssm.update", "layers/gmu", "layers/mlp",
                  "sample"):
        assert scope in text, scope


def test_decoder_hybrid_check_sees_a_leaf_transposed_for_its_write(chip):
    """The same check on the write this family had first (a scatter
    indexed by row and position ACROSS the pairs' axis, for which the
    compiler transposed the whole leaf there and back at every step:
    4.7 GB of copies a step, PERF.md PR 45) finds the copy, and does not
    find one for ``_write``: the test above cannot pass by looking past
    it."""
    from containerpilot_tpu.models import decoder_hybrid as dh

    slots, pairs, rows, width = 64, 10, 512, 128
    leaf = jax.ShapeDtypeStruct((slots, pairs, rows, width), jnp.bfloat16,
                                sharding=chip)
    new = jax.ShapeDtypeStruct((slots, pairs, 1, width), jnp.bfloat16,
                               sharding=chip)
    at = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)

    def across(leaf, new, at):
        return leaf.at[jnp.arange(slots), :, at].set(new[:, :, 0])

    def moved(compiled):
        """Ring-sized results of a copy or a transpose outside fused
        computations."""
        return [
            line for _c, line in _outside_fusions(compiled.as_text())[0]
            if re.search(r" (copy|transpose)\(", line)
            and f"bf16[{slots},{pairs},{rows},{width}]" in line.split("=")[1]]

    ring_bytes = slots * pairs * rows * width * 2
    old = jax.jit(across, donate_argnums=(0,)).lower(leaf, new, at).compile()
    assert moved(old)
    assert old.memory_analysis().temp_size_in_bytes >= ring_bytes
    now = jax.jit(dh._write, donate_argnums=(0,)).lower(leaf, new, at).compile()
    assert moved(now) == []
    assert now.memory_analysis().temp_size_in_bytes < 1024 ** 2


def test_decoder_hybrid_check_sees_a_whole_plane_contraction(chip, monkeypatch):
    """The same reading of the read this family had first (the plain
    contraction under ``attn.cross``: every row of the plane to its
    3,072nd position, masked afterwards; 8.05 GB a step of which 3.2
    were live, PERF.md PR 45) finds the fusions that take the whole
    plane, and finds none, and one kernel call, for
    ``_plane_attention``: the test above cannot pass by looking past
    the read."""
    from containerpilot_tpu.models import decoder_hybrid as dh
    from containerpilot_tpu.ops import flash

    monkeypatch.setattr(flash, "_resolve_interpret", lambda i: False)
    slots, length = 64, 3072
    cfg, _shapes = _decoder_hybrid_shapes(chip, slots, length)
    pairs, width = cfg.kv_pairs, cfg.pair_dim
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=chip)
    q = shape((slots, 1, pairs, 2 * cfg.n_heads // cfg.n_kv_heads, width),
              jnp.bfloat16)
    plane = shape((slots, pairs, length, width), jnp.bfloat16)
    at = shape((slots,), jnp.int32)

    def old(q, keys, values, at):
        in_plane = (jnp.arange(length)[None, :] <= at[:, None])[:, None, :]
        with dh._scoped("cross"):
            return dh._pair_attention(q, keys, values, in_plane, cfg)

    def now(q, keys, values, at):
        with dh._scoped("cross"):
            return dh._plane_attention(q, keys, values, at, cfg)

    whole = f"bf16[{slots},{pairs},{length},{width}]"
    text = jax.jit(old).lower(q, plane, plane, at).compile().as_text()
    assert _plane_contractions(text, whole) and _plane_kernels(text) == []
    compiled = jax.jit(now).lower(q, plane, plane, at).compile()
    text = compiled.as_text()
    assert _plane_contractions(text, whole) == []
    assert _plane_kernels(text) == ["attn.cross"]
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 1024 ** 2


@pytest.mark.parametrize("prompt", [512, 1536])
def test_decoder_hybrid_prefill_fits_beside_the_pool(chip, prompt):
    """The cell's two prefill programs at the published size: the
    weights, a row of 39.9 MB and under a gibibyte of temporaries (the
    scan runs in blocks of 16 positions and holds no [prompt, 5120, 16]
    array: 0.5 GB a layer at 1,536), so that weights + pool + a row in
    flight + the program's temporaries stay under 0.8 of the chip."""
    from containerpilot_tpu.models.decode import prefill

    slots, length = 64, 3072
    cfg, (params, _pool, _state) = _decoder_hybrid_shapes(chip, slots, length)
    tokens = jax.ShapeDtypeStruct((1, prompt), jnp.int32, sharding=chip)
    memory = jax.jit(lambda p, t: prefill(p, t, cfg, length)).lower(
        params, tokens).compile().memory_analysis()
    assert (DECODER_HYBRID_ROW <= memory.output_size_in_bytes
            < DECODER_HYBRID_ROW + 2 * 1024 ** 2)
    assert memory.temp_size_in_bytes < 1.0 * GIB
    weights = memory.argument_size_in_bytes
    assert 7.70e9 < weights < 7.72e9
    assert (weights + slots * DECODER_HYBRID_ROW + DECODER_HYBRID_ROW
            + memory.temp_size_in_bytes) < 0.8 * HBM_BYTES
