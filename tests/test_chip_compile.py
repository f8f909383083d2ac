"""What only the chip's compiler can say, with no chip attached.

The TPU compiler is installed here and compiles for a chip that is
described (``v5e:2x2``) and not attached: it refuses what the chip would
refuse — a kernel whose blocks do not fit the tiling or the fast memory, a
program that does not fit HBM, a Mosaic kernel left to GSPMD to partition.
Nothing runs, so these tests say nothing of results or time; the numeric
test at the end runs the same kernels in interpret mode against
``mha_reference``.

``ops.attention._on_tpu`` asks JAX for its default backend and sees the CPU
during such a compile, so the tests steer it here, not through an option of
the program.
"""

import functools
import itertools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import transformer as T
from ray_tpu.ops import attention as A
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.train import step as S

# the flagship cell (chip_smoke.py WIDTHS); depth is cut, the layer stack
# is one scanned body whatever its length
WIDTHS = dict(hidden=2048, mlp_hidden=5632, layers=2, heads=16, kv_heads=16,
              max_seq=2048, param_dtype=jnp.bfloat16)
BATCH, SEQ = 8, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(scope="module", autouse=True)
def _for_the_described_chip():
    """Kernels on, persistent compilation cache off: an executable compiled
    for a described chip is written to the cache but cannot be read back
    without one, and the next compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setattr(A, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


def _on(sharding, tree):
    """Shapes placed on described devices (nothing can be device_put there)."""
    if not isinstance(sharding, (dict, list, tuple)):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), tree, sharding)


def _qkv(topo, shape=(BATCH, SEQ, 16, 128)):
    one = SingleDeviceSharding(topo.devices[0])
    return (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one),) * 3


def _pallas_calls(text):
    """[(HLO name, `trace_reduce`'s tag)] of every Pallas call of a compiled
    program: what the benchmark's readers find the kernels by."""
    from benchmarks import trace_reduce

    return [(trace_reduce.short_name(line.strip()), trace_reduce.op_tag(line))
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and " = " in line]


def _flash_forward_calls(text) -> list:
    """The names of a compiled prefill's flash calls, each one the forward
    kernel's Mosaic call of three inputs."""
    calls = [(op, tag) for op, tag in _pallas_calls(text) if "flash" in op]
    for op, tag in calls:
        assert op.startswith("flash_attention_fwd"), calls
        assert tag.startswith("tpu_custom_call/3in"), calls
    return [op for op, _ in calls]


def test_flash_forward_compiles(topo):
    compiled = jax.jit(lambda q, k, v: A._flash_fwd_pallas(
        q, k, v, True, None, A.FLASH_BLOCK_Q, A.FLASH_BLOCK_K)).lower(
            *_qkv(topo)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def _flash_loss(q, k, v):
    return A.flash_attention(q, k, v).astype(jnp.float32).sum()


def test_flash_backward_compiles(topo):
    compiled = jax.jit(jax.grad(_flash_loss, argnums=(0, 1, 2))).lower(
        *_qkv(topo)).compile()
    # forward (for its residuals), dQ pass, dK/dV pass
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("shape", [(4, 2048, 32, 128), (2, 2048, 16, 128)],
                         ids=["one-chip-cell", "four-chip-cell"])
def test_flash_kernels_compile_at_the_train_cells_shapes(topo, shape):
    """The three kernels at the shapes the two train cells call them with
    (the four-chip cell's is a device's share under `make_attn_fn`'s
    `shard_map`) and the default blocks: one forward call of 3 inputs and
    two backward calls of 6 inputs under the names the benchmark's readers
    (`benchmarks/readers.py::FLASH_*`) and the step's test find them by."""
    assert (A.FLASH_BLOCK_Q, A.FLASH_BLOCK_K) == (512, 512)
    compiled = jax.jit(jax.grad(_flash_loss, argnums=(0, 1, 2))).lower(
        *_qkv(topo, shape)).compile()
    calls = _pallas_calls(compiled.as_text())
    # the HLO wraps a kernel's name in what differentiated it
    # (`jvp_flash_attention_fwd_.3`, `transpose_jvp_flash_attention_bwd_dq__.5`)
    found = sorted((re.search(r"flash_attention_(fwd|bwd_dq|bwd_dkv)", name)[0],
                    tag.rsplit("/", 1)[0]) for name, tag in calls)
    assert found == [("flash_attention_bwd_dkv", "tpu_custom_call/6in"),
                     ("flash_attention_bwd_dq", "tpu_custom_call/6in"),
                     ("flash_attention_fwd", "tpu_custom_call/3in")], calls


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_flash_kernel_bodies_multiply_what_arrives_and_transpose_nothing():
    """The block step of the three kernels by their jaxprs at a bf16 shape
    with bare, masked and skipped blocks: no `transpose`, and every
    `dot_general` takes bf16 operands (q, k, v, dO as they arrive, `p` and
    `ds` cast to them) and sums in float32."""
    shape = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(_flash_loss, argnums=(0, 1, 2)))(
        shape, shape, shape)
    kernels = {e.params["name"]: e
               for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"}
    assert sorted(kernels) == ["flash_attention_bwd_dkv",
                               "flash_attention_bwd_dq", "flash_attention_fwd"]
    dots = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
            "flash_attention_bwd_dkv": 4}
    for name, call in kernels.items():
        body = list(_eqns(call.params["jaxpr"]))
        assert "transpose" not in {e.primitive.name for e in body}, name
        products = [e for e in body if e.primitive.name == "dot_general"]
        # each product once in the body without a mask and once in the
        # body with it (no padding here: dK/dV's masked walk behind the
        # bare one is empty at trace time and not there)
        assert len(products) == 2 * dots[name], (name, len(products))
        for e in products:
            assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2, name
            assert e.outvars[0].aval.dtype == jnp.float32, name


# the train cells' widths (`benchmarks/configs/mistral7b-v03-lora-*.json`:
# Mistral-7B-v0.3, rank 16, a step of 4 x 2048) at the depth each cell runs
BENCH_WIDTHS = dict(vocab_size=32768, hidden=4096, mlp_hidden=14336, heads=32,
                    kv_heads=8, head_dim=128, max_seq=2048, rope_theta=1e6,
                    tie_embeddings=False, param_dtype=jnp.bfloat16)
MESHES = {"one": (MeshSpec(), 1), "four": (MeshSpec(fsdp=2, tensor=2), 4)}
# temporaries a device of the step that differentiated every leaf (commit
# 5bf1d99, `benchmarks/rehearse.py`): the frozen stacks' gradients were in it
PARENT_TEMP_BYTES = {"one": 8.274e9, "four": 10.938e9}
# and of the step that keeps the block's input alone (the bare checkpoint,
# `T.REMAT_LADDER[-1]`: commit 52fc031, and this tree's last rung)
BARE_TEMP_BYTES = {"one": 2.959e9, "four": 2.71e9}
# What the compiler's final count adds to a step that keeps names, beyond
# their bytes: its buffer assignment (`--xla_dump_to`) charges the names
# exactly their bytes (5.652 GB on one chip), the count it reports 1.27 GB
# more than that on one chip and less than that on four. No array of the
# program is behind it (PERF.md section 6, PR 46).
FINAL_COUNT_SLACK_BYTES = 1.35e9
# how (batch, heads and MLP width) are cut on a device of each mesh
CUTS = {"one": (1, 1), "four": (2, 2)}


def _kept_bytes(cfg, chips, batch):
    """A device's bytes of what `T.REMAT_LADDER[0]` keeps beside the block's
    input, all layers, from the shapes: q, k, v, the kernel's out and lse,
    gate, up, the residual after attention, the adapters' `x @ a`."""
    by_batch, by_heads = CUTS[chips]
    rows = batch // by_batch * SEQ
    heads = 2 * cfg.heads * cfg.hd + 2 * cfg.kv_heads * cfg.hd  # q, out, k, v
    a_layer = (2 * rows * (heads + 2 * cfg.mlp_hidden) // by_heads
               + 4 * rows * cfg.heads // by_heads  # lse, float32
               + 2 * rows * cfg.hidden + 3 * 2 * rows * cfg.lora_rank)
    return cfg.layers * a_layer


@pytest.fixture(scope="module")
def train_steps(topo):
    """(chips, widths, lora) -> (compiled step, state shapes, cfg, the step
    `make_train_step` returned), each compiled once: "flagship" is `WIDTHS` with a step of 8 x 2048, "bench"
    the train cells' own; `lora=False` is the same widths trained dense."""
    @functools.cache
    def compiled(chips, widths="flagship", lora=True):
        if widths == "flagship":
            kw, batch = dict(WIDTHS), BATCH
        else:
            kw, batch = dict(BENCH_WIDTHS, layers={"one": 8, "four": 32}[chips]), 4
        cfg = T.config("llama2_7b_lora", **kw, lora_rank=16 if lora else 0)
        spec, count = MESHES[chips]
        mesh = build_mesh(spec, list(topo.devices)[:count])
        opt = S.default_optimizer(cfg)
        step = S.make_train_step(cfg, opt, mesh)
        state = _on(step._shardings, jax.eval_shape(
            lambda: S.fresh_state(cfg, opt, jax.random.key(0))))
        tokens = {"tokens": jax.ShapeDtypeStruct(
            (batch, SEQ), jnp.int32, sharding=step._batch_sharding)}
        with jax.set_mesh(mesh):
            return step._jitted.lower(state, tokens).compile(), state, cfg, step

    return compiled


def _arg_bytes(state):
    return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(state))


def test_lora_step_compiles_for_one_chip(train_steps):
    compiled, state, _, _ = train_steps("one")
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= _arg_bytes(state)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_lora_step_compiles_for_four_chips(train_steps):
    """The README's headline — one GSPMD program, attention on the Pallas
    kernels — on a real 2x2: refused before PR 21 ("Mosaic kernels cannot
    be automatically partitioned"), because the dense path called the
    kernel outside shard_map. Also the first time create_device_mesh meets
    the 7-axis shape on a described 2x2."""
    compiled, state, _, _ = train_steps("four")
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the frozen weights gathered over `fsdp`, a block's rows carried round
    # `tensor` (PR 55: the whole sums of the stream are rings' transfers)
    assert "all-gather" in text and "collective-permute-start" in text
    # every large leaf is sharded over fsdp x tensor: a device holds about
    # a quarter of the state (norms and the step counter are replicated)
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0.25 <= per_device / _arg_bytes(state) < 0.27


@pytest.mark.parametrize("chips", ["one", "four"])
def test_lora_step_executes_no_frozen_gradient(train_steps, chips):
    """Forward 2N, the activations' gradient 2N: 4N, and no recomputed
    forward since the checkpoint keeps what the backward reads (PR 46; 6N
    with the bare checkpoint). The dense step of the same widths executes
    6N: those and every weight's gradient (8N with the bare checkpoint).
    The share reads the compiler's operation counts of the two compiled
    flagship steps (a loop's body counted once: a layer's and the head's):
    4N / 6N, 0.669 here; 0.70 with the bare checkpoint, 6N / 8N where the
    head, which no checkpoint wraps, is 4N / 6N."""
    lora, _, _, _ = train_steps(chips)
    dense, _, _, _ = train_steps(chips, lora=False)
    share = lora.cost_analysis()["flops"] / dense.cost_analysis()["flops"]
    print(f"{chips}: LoRA step / dense step = {share:.3f} of the operations")
    assert 0.6 < share <= 0.8


KEPT_LIMITS = {  # operations (today 1.421e13 / 3.556e12), bytes a device
    "one": (1.20e13, 14.5e9), "four": (3.0e12, 12.6e9)}


@pytest.mark.parametrize("chips", ["one", "four"])
def test_lora_step_runs_no_matmul_and_no_flash_forward_twice(train_steps, chips):
    """The train cells' own step (PR 46): every block's checkpoint keeps the
    richest rung of `T.REMAT_LADDER`, so the compiled step holds ONE flash
    forward call (two with the bare checkpoint: the backward ran it again
    for `lse`) beside the two backward kernels, executes a layer's forward
    once (1.159e13 operations a layer and the head on one chip for
    1.421e13, 2.900e12 for 3.556e12 on four), and still fits the chip."""
    compiled, _, _, step = train_steps(chips, "bench")
    assert step.remat_kept == T.REMAT_LADDER[0]
    assert set(A.FLASH_KEPT) < set(step.remat_kept)
    text = compiled.as_text()
    assert len(re.findall(r"%flash_attention_fwd[.\d]* = ", text)) == 1
    assert text.count("tpu_custom_call") == 3
    flops, fits = KEPT_LIMITS[chips]
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{chips}: {compiled.cost_analysis()['flops']:.4e} operations, "
          f"{mem.argument_size_in_bytes / 1e9:.3f} + "
          f"{mem.temp_size_in_bytes / 1e9:.3f} = {total / 1e9:.3f} GB")
    assert compiled.cost_analysis()["flops"] < flops
    assert total < fits


def _computations(text):
    """{name: lines} of a compiled program's computations."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head[1]
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return {name: "\n".join(lines) for name, lines in out.items()}


def _scanned_bodies(text):
    """The text of every `while` body of a compiled program and of the
    computations a body calls: what runs once a layer."""
    comps = _computations(text)
    todo = [body for comp in comps.values()
            for body in re.findall(r" while\(.*?body=%([\w.\-]+)", comp)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                           comps[name])
    return "\n".join(comps[name] for name in sorted(seen))


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


@pytest.mark.parametrize("chips", ["one", "four"])
def test_lora_step_sums_the_stream_over_tensor_in_rings(train_steps, chips):
    """The train cells' own step (PR 55). On `fsdp=2 x tensor=2` a block's
    rows lie over `tensor` between its sublayers and its projections are
    rings of 2 turns over 1,024 rows: the scanned bodies hold no `all-reduce`
    (nor `reduce-scatter`) of the whole residual `bf16[2,2048,4096]` (four a
    layer before), and half the rows travel in `collective-permute`s that are
    `-start` / `-done` pairs, so a product can run between them; the
    checkpoint still keeps its richest rung. On one chip nothing is cut and
    the step holds no collective at all."""
    compiled, _, _, step = train_steps(chips, "bench")
    text = compiled.as_text()
    found = set(re.findall(
        r" (%s)(?:-start|-done)?\(" % "|".join(COLLECTIVES), text))
    assert step.remat_kept == T.REMAT_LADDER[0]
    if chips == "one":
        assert step.tensor_ring is None
        assert not found
        return
    assert step.tensor_ring == {"rings": 4, "turns": 2, "rows": 1024}
    bodies = _scanned_bodies(text)
    assert "tpu_custom_call" in bodies  # the layers' bodies, not a helper's
    whole = [(name, shape) for name, shape in _summed(bodies)
             if shape == "bf16[2,2048,4096]"]
    assert not whole, whole
    half = r"bf16\[2,1024,4096\]\S*"
    starts = re.findall(rf"= \({half}, [^=]*\) collective-permute-start\(",
                        bodies)
    dones = re.findall(rf"= {half} collective-permute-done\(", bodies)
    alone = re.findall(rf"= {half} collective-permute\(", bodies)
    print(f"four: {len(starts)} transfers of half the rows in the bodies")
    # a layer's forward and backward: four rings each, one transfer a ring
    assert len(starts) == len(dones) >= 8 and not alone


def _summed(text):
    """(name, element type and dimensions) of every array an `all-reduce` or
    a `reduce-scatter` of a compiled program returns, alone or in a tuple."""
    lines = re.findall(
        r"%(\S+) = (.*?) (?:all-reduce|reduce-scatter)(?:-start)?\(", text)
    return [(name, shape) for name, shapes in lines
            for shape in re.findall(r"\w+\[[\d,]*\]", shapes)]


def _frozen_shapes(state, cfg):
    """Result types a frozen leaf's gradient could have on a device: the
    leaf whole or one layer of its stack, each dimension whole or as the
    leaf's sharding cuts it (a gradient is summed before it is scattered)."""
    shapes = set()
    mask = T.trainable_mask(cfg, state["params"])
    for leaf, trains in zip(jax.tree.leaves(state["params"]),
                            jax.tree.leaves(mask)):
        if trains or leaf.ndim < 2:
            continue  # a norm's [hidden] is also a row of activations
        cut = leaf.sharding.shard_shape(leaf.shape)
        for dims in itertools.product(*zip(leaf.shape, cut)):
            for lead in {dims[0], 1} if leaf.ndim > 2 else {dims[0]}:
                shapes.add("bf16[" + ",".join(map(str, (lead,) + dims[1:])) + "]")
    return shapes


@pytest.mark.parametrize("chips", ["one", "four"])
def test_lora_step_keeps_no_frozen_gradient(train_steps, chips):
    """The train cells' own step (PR 45): the stacked gradients of the
    frozen base are not among its temporaries (2.8 GB for the MLP's on a
    device of either cell, 5.3 and 8.2 GB for all of them), nothing with a
    frozen stack's shape is computed or written (the stacks are parameters,
    loop state, and the relayout and the gather the forward asks for), and
    no collective sums anything of a frozen leaf's shape."""
    compiled, state, cfg, _ = train_steps(chips, "bench")
    mem = compiled.memory_analysis()
    mlp = sum(2 * math.prod(w.sharding.shard_shape(w.shape))
              for w in (state["params"]["blocks"][k]
                        for k in ("wi_gate", "wi_up", "wo_mlp")))
    kept = _kept_bytes(cfg, chips, batch=4)
    print(f"{chips}: temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB "
          f"(the bare checkpoint's {BARE_TEMP_BYTES[chips] / 1e9:.3f} + "
          f"{kept / 1e9:.3f} of kept names), a device's MLP stacks "
          f"{mlp / 1e9:.3f} GB")
    # the temporaries are the bare checkpoint's (which held no frozen
    # gradient: under the older step's by the MLP stacks and more) and the
    # names the checkpoint keeps since PR 46, counted from their shapes
    assert BARE_TEMP_BYTES[chips] < PARENT_TEMP_BYTES[chips] - mlp
    assert mem.temp_size_in_bytes < (BARE_TEMP_BYTES[chips] + kept
                                     + FINAL_COUNT_SLACK_BYTES)
    # the donated state comes out in the buffers it went in by
    assert mem.alias_size_in_bytes >= 0.999 * mem.argument_size_in_bytes
    frozen = _frozen_shapes(state, cfg)
    text = compiled.as_text()
    moved_only = {"parameter", "get-tuple-element", "bitcast", "copy",
                  "copy-start", "copy-done", "all-gather", "all-gather-start",
                  "all-gather-done", "custom-call"}
    made = [(name, dtype, dims, op) for name, dtype, dims, op in _results(text)
            if op not in moved_only and dims[:1] != [1]
            and f"{dtype}[{','.join(map(str, dims))}]" in frozen]
    assert not made, made
    summed = [(name, shape) for name, shape in _summed(text) if shape in frozen]
    assert not summed, summed


# the serve cells' configurations (`benchmarks/configs/*-serve-*.json`):
# published widths, depth cut, 16 cache slots of 2048 positions
SERVE_SLOTS = 16
SERVE_CONFIGS = {
    "mistral7b-v03-serve-d16": dict(
        name="llama2_7b", vocab_size=32768, hidden=4096, mlp_hidden=14336,
        layers=16, heads=32, kv_heads=8, head_dim=128, max_seq=SEQ,
        rope_theta=1e6, tie_embeddings=False, param_dtype=jnp.bfloat16),
    "olmoe-1b-7b-serve-d8": dict(
        name="olmoe_1b_7b", layers=8, param_dtype=jnp.bfloat16),
    # 32 slots, and its traffic's prefill bucket is 1024
    "zaya1-8b-serve-d16": dict(
        name="zaya1_8b", layers=16, param_dtype=jnp.bfloat16, slots=32,
        bucket=1024),
    # Laguna-S-2.1's published widths, one chip's share of a layer that two
    # hold: 5 of 48 layers, 128 of 256 experts, half the vocabulary; 32
    # slots of 4096 positions, the 2048 bucket
    "laguna-s-2.1-serve-ep2-d5": dict(
        name="laguna_debug", vocab_size=50176, hidden=3072, mlp_hidden=1024,
        layers=5, heads=48, kv_heads=8, head_dim=128, max_seq=1048576,
        num_experts=256, experts_per_token=10, experts_held=(0, 128),
        window=512, window_heads=72, dense_mlp_hidden=12288,
        shared_expert_hidden=1024,
        rope_yarn=(128.0, 8192.0, 32.0, 1.0, 1.4852030263919618),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, slots=32, max_len=4096),
}


# Kimi-Linear-48B-A3B-Instruct's published widths AND depth (27 layers), one
# chip's share of a layer that sixteen hold: 16 of 256 experts, an eighth of
# the vocabulary; 32 slots of 4096 positions, the 2048 bucket. Not among
# SERVE_CONFIGS: its cache has no K/V rows for the tests that walk those
KIMI_LINEAR = "kimi-linear-48b-a3b-serve-ep16"
LONGCAT = "longcat-flash-serve-ep32-d4"
NEMOTRON_H = "nemotron-3-super-serve-ep8-d22"
MIMO = "mimo-v2-flash-serve-ep16-d11"
JAMBA = "jamba2-3b-serve-whole"
OURO = "ouro-2.6b-serve-whole"
SOLAR = "solar-open2-250b-serve-ep16-d8"
PATTERN_CONFIGS = {
    # Solar-Open2-250B's published widths, layers 0-7 of 48 (two periods of
    # one gated GQA layer and three delta-rule layers of 64 heads), one
    # chip's share of a layer that sixteen hold: 20 of 320 experts, an eighth
    # of the vocabulary; 16 slots of 8704 positions, the 8192 bucket
    SOLAR: dict(
        name="solar_open2_debug", vocab_size=24576, hidden=4096,
        mlp_hidden=1280, layers=8, heads=64, kv_heads=8, head_dim=128,
        max_seq=1048576, num_experts=320, experts_per_token=8,
        experts_held=(0, 20), shared_expert_hidden=1280,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, slots=16, max_len=8704,
        bucket=8192),
    # Ouro-2.6B whole (the ONE block, looped; here and not among
    # SERVE_CONFIGS because the tests that walk those hold 16 slots x 2048):
    # its published widths, all 48 layers run four times, the whole
    # vocabulary; 8 slots of 512 positions, the 256 bucket
    OURO: dict(
        name="ouro_debug", vocab_size=49152, hidden=2048, mlp_hidden=5632,
        layers=48, heads=16, kv_heads=16, head_dim=128, max_seq=65536,
        loop_steps=4, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, slots=8,
        max_len=512, bucket=256),
    # MiMo-V2-Flash's published widths, layers 0-10 of 48 (full, 4 window,
    # full, 5 window), one chip's share of a layer that sixteen hold: 16 of
    # 256 experts, an eighth of the vocabulary; 32 slots of 10240 positions,
    # the 8192 bucket
    MIMO: dict(
        name="mimo_v2_debug", vocab_size=19072, hidden=4096, mlp_hidden=2048,
        layers=11, heads=64, kv_heads=4, head_dim=192, max_seq=262144,
        num_experts=256, experts_per_token=8, experts_held=(0, 16),
        layer_kinds=("full",) + ("window",) * 4 + ("full",)
        + ("window",) * 5, window=128, window_heads=64, window_kv_heads=8,
        value_dim=128, dense_mlp_hidden=16384, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, slots=32, max_len=10240, bucket=8192),
    KIMI_LINEAR: dict(
        name="kimi_linear_debug", vocab_size=20480, hidden=2304,
        mlp_hidden=1024, layers=27, heads=32, kv_heads=32, head_dim=128,
        max_seq=1048576, num_experts=256, experts_per_token=8,
        experts_held=(0, 16), mla_latent=512, mla_rope_dim=64,
        dense_mlp_hidden=9216, shared_expert_hidden=1024,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, slots=32, max_len=4096),
    # LongCat-Flash-Chat's published widths, 4 of 28 double layers, one
    # chip's share of a layer that 32 hold: 16 of 512 routed experts beside
    # the 256 zero-compute outputs, an eighth of the vocabulary; 32 slots of
    # 5120 positions, the 4096 bucket
    LONGCAT: dict(
        name="longcat_debug", vocab_size=16384, hidden=6144, mlp_hidden=2048,
        layers=4, heads=64, kv_heads=64, head_dim=128, max_seq=131072,
        num_experts=512, zero_experts=256, experts_per_token=12,
        experts_held=(0, 16), mla_latent=512, mla_rope_dim=64,
        mla_q_rank=1536, mla_scales=(2.0, 12 ** 0.5), dense_mlp_hidden=12288,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, slots=32, max_len=5120,
        bucket=4096),
    # NVIDIA-Nemotron-3-Super-120B-A12B's published widths, the first 22 of
    # its 88 one-sublayer layers, one chip's share of a layer that 8 hold: 64
    # of 512 experts, an eighth of the vocabulary; 32 slots of 2048
    # positions, the 512 bucket
    NEMOTRON_H: dict(
        name="nemotron_h_debug", vocab_size=16384, hidden=4096,
        mlp_hidden=2688, layers=22, heads=32, kv_heads=2, head_dim=128,
        max_seq=262144, num_experts=512, experts_per_token=22,
        experts_held=(0, 64), shared_expert_hidden=5376, moe_latent=1024,
        layer_kinds=tuple({"M": "ssm", "*": "gqa", "E": "lmoe"}[c]
                          for c in "MEMEMEM*EMEMEMEM*EMEME"),
        ssm_heads=128, ssm_head_dim=64, ssm_groups=8, ssm_state=128,
        ssm_chunk=128, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        slots=32, max_len=2048, bucket=512),
    # AI21-Jamba2-3B whole: its published widths, all 28 layers (a mixer or
    # an attention and then an MLP: 56 sublayers), the whole vocabulary, the
    # head tied; 16 slots of 12288 positions, the 8192 bucket
    JAMBA: dict(
        name="jamba_debug", vocab_size=65536, hidden=2560, mlp_hidden=8192,
        layers=56, heads=20, kv_heads=1, head_dim=128, max_seq=262144,
        layer_kinds=(("ssm1", "mlp") * 7 + ("gqa", "mlp")
                     + ("ssm1", "mlp") * 6) * 2,
        ssm_heads=5120, ssm_dt_rank=160, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, slots=16, max_len=12288, bucket=8192),
}


@pytest.fixture(scope="module")
def serve_programs(topo):
    """configuration name -> (cfg, prefill[bucket], decode[slots x SEQ],
    cache shapes) of a `ContinuousBatcher`, compiled once for one described
    chip: the programs as the engine jits them, the decode step's donation
    of its cache included, and the weights in the layouts the decode step
    chooses for them (`_compile_decode`, as the engine's build does before
    any prefill program is made): what `.lower(<plain shapes>)` of either
    compiles is the program an engine runs."""
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.models.decoding import init_cache

    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    @functools.cache
    def engine(name):
        widths = dict({**SERVE_CONFIGS, **PATTERN_CONFIGS}[name])
        slots = widths.pop("slots", SERVE_SLOTS)
        bucket = widths.pop("bucket", SEQ)
        max_len = widths.pop("max_len", SEQ)
        cfg = T.config(widths.pop("name"), **widths)
        params = _on(one, jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.key(0))))
        batcher = ContinuousBatcher.__new__(ContinuousBatcher)  # programs only
        batcher.cfg, batcher.max_len, batcher.slots = cfg, max_len, slots
        batcher._jit_programs()
        batcher._compile_decode(params)
        return batcher, params, bucket

    @functools.cache
    def prefill_at(name, bucket):
        """The configuration's prefill program of another bucket than its
        cell's; what it attended with is in `attention_paths(name)`."""
        batcher, params, _ = engine(name)
        lowered = batcher._prefill_program(bucket).lower(  # as `_prefill_into`
            params, arr((1, bucket), jnp.int32), arr((1,), jnp.int32))
        prefill_modules[name, bucket] = lowered.as_text()
        return lowered.compile()

    @functools.cache
    def compiled(name):
        batcher, params, bucket = engine(name)
        cfg, slots, max_len = batcher.cfg, batcher.slots, batcher.max_len
        prefill = prefill_at(name, bucket)
        cache = _on(one, jax.eval_shape(
            lambda: init_cache(cfg, slots, max_len)))
        decode = batcher._decode_jit.lower(
            params, arr((slots,), jnp.int32), cache,
            _on(one, jax.eval_shape(lambda: jax.random.key(0))),
            arr((slots,), jnp.float32), arr((slots,), jnp.int32),
            arr((slots,), jnp.bool_)).compile()
        # what the engine's `moe_grouped_path` would say of the two programs
        grouped_paths[name] = batcher.moe_grouped_path
        return cfg, prefill, decode, cache

    compiled.grouped_paths = grouped_paths = {}
    # (name, bucket) -> the module a prefill program handed the compiler
    compiled.prefill_modules = prefill_modules = {}
    compiled.engine = engine
    compiled.prefill_at = prefill_at
    # what the engine's `prefill_attention_path` says of the prefills so far
    compiled.attention_paths = lambda name: engine(name)[0].prefill_attention_path
    # and its `kda_path` of the delta-rule layers' two choices
    compiled.kda_paths = lambda name: engine(name)[0].kda_path
    return compiled


def _total_bytes(program):
    """What the program needs on the device: an argument that is aliased to
    an output (a donated one) is one buffer, not two."""
    m = program.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _entry_parameters(program) -> int:
    """Operands of a compiled program: `parameter(i)` lines of its ENTRY."""
    entry = program.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    return len(re.findall(r" parameter\(\d+\)", entry))


@pytest.mark.parametrize("name", ["mistral7b-v03-serve-d16",
                                  "olmoe-1b-7b-serve-d8"])
def test_prefill_and_decode_compile(serve_programs, name):
    """The decode step of both serve configurations is given its cache to
    keep: the cache comes out in the buffers it went in by (2.15 GB
    aliased), no layer of it is ever a temporary (67 MB dense, 134 MB
    sparse: before PR 26 the layer scan sliced each layer into a private
    copy and wrote the whole layer back into a second stack, 42-45% of the
    step on the chip), and nothing updates a slice of the stack but the
    scatter of one row per slot. Sampling's conditionals stand between the
    logits and the tokens and cost none of this: outside their branches are
    the [16, vocabulary] logits and the argmax; the scaled copy, the draw
    and the sort are inside, where a greedy step does not go."""
    cfg, prefill, decode, cache = serve_programs(name)
    cache_bytes = _arg_bytes((cache.k, cache.v))
    m = decode.memory_analysis()
    print(f"{name} decode[16x2048]: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} + outputs "
          f"{m.output_size_in_bytes / 1e9:.2f} + temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f} = "
          f"{_total_bytes(decode) / 1e9:.2f} GB")
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < cache_bytes / 2 / cfg.layers
    assert _total_bytes(decode) < 10e9
    stack = "bf16[" + ",".join(map(str, cache.k.shape)) + "]"
    # `%name = shape{layout} op(`: a fusion is named after what it holds
    written = re.findall(r"%(\S+) = (\S+) ([\w-]+)\(", decode.as_text())
    assert [n for n, shape, op in written if shape.startswith(stack)
            and "scatter" in n + op]
    assert not [n for n, shape, op in written if shape.startswith(stack)
                and "dynamic-update-slice" in n + op]
    assert _total_bytes(prefill) < 16e9
    # PR 30's optional state and router carry are None here: the programs
    # take the parameters, the three cache arrays and what a step is given
    # (tokens, key, temperatures, top-ks, mask; tokens and length), no more
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    assert cache.state is None
    assert _entry_parameters(decode) == leaves + 3 + 5
    assert _entry_parameters(prefill) == leaves + 2


def test_dense_decode_keeps_no_projection_in_a_buffer_of_its_own(
        serve_programs):
    """The chat and document cells' decode step (Mistral-7B, 16 layers): the
    layer body slices no layer of `wq`, `wk` or `wv` out of its stack into a
    buffer of its own. The stacks lie on the chip tiled by head (`wq`, `wk`,
    `wv` (0, 2, 1, 3), the decode program's own choice: `Layout.AUTO`), so
    the stack's `dynamic-slice` sits inside the product's fusion; in the
    default tiling a `constant_dynamic-slice_fusion` wrote
    bf16[1,4096,32,128] and two bf16[1,4096,8,128] every layer for the
    product to re-tile and read: a tenth of the step on the chip (PR 65's
    ledger lines)."""
    name = "mistral7b-v03-serve-d16"
    _, _, decode, _ = serve_programs(name)
    comps = _computations(decode.as_text())
    bodies = {body for comp in comps.values() for body in
              re.findall(r" while\(.*?body=%([\w.\-]+)", comp)}
    assert bodies
    for body in bodies:
        for op_name, dtype, dims, op in _results(comps[body]):
            assert dims not in ([1, 4096, 32, 128], [1, 4096, 8, 128]), (
                op_name, dims, op)
    chosen = serve_programs.engine(name)[0]._formats["blocks"]
    assert {w: chosen[w].layout.major_to_minor for w in ("wq", "wk", "wv")} \
        == dict.fromkeys(("wq", "wk", "wv"), (0, 2, 1, 3))


@pytest.mark.parametrize("name", [*SERVE_CONFIGS, *PATTERN_CONFIGS])
def test_lowering_with_plain_shapes_compiles_the_program_that_runs(
        serve_programs, name):
    """The readers' contract, for every serve configuration here: the
    benchmark's runners map device operations to scopes by
    `batcher._decode_jit.lower(<shapes that keep a leaf's sharding and
    nothing else>).compile()` and `batcher._prefill_jits[bucket].lower(...)`
    (`benchmarks/runners/serve_kimi_linear.py::_like`), so what those compile
    must be compiled for the formats the decode step chose and hold the
    operations of the programs that run: the executable the decode step was
    compiled to with `Layout.AUTO` for its weights (the same instructions;
    the NUMBERS in a few names, `%copy.27` / `%copy.28`, shift by one where
    the other operands' shardings are said, as the readers say them and a
    step's own arguments never did), and the prefill a `jit` is handed to
    compile when it is called with weights that lie in those formats (the
    same module to the character)."""
    def names(program):  # without their numbers
        return sorted(re.sub(r"\.\d+", "", n)
                      for n, *_ in _results(program.as_text()))

    def chosen(program):
        return jax.tree.map(
            lambda was, now: was.layout is None or was == now,
            batcher._formats, program.input_formats[0][0])

    batcher, params, bucket = serve_programs.engine(name)
    _, prefill, decode, _ = serve_programs(name)
    assert all(jax.tree.leaves(chosen(decode)))
    assert all(jax.tree.leaves(chosen(prefill)))
    assert names(decode) == names(batcher._decode_jit._run)
    one = jax.tree.leaves(params)[0].sharding
    laid = jax.tree.map(
        lambda a, told: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=told if told.layout is not None else a.sharding),
        params, batcher._formats)
    rest = (jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one))
    # the module the compiler is handed, character for character (lowered
    # and not compiled again: one module's compile is the fixture's)
    assert serve_programs.prefill_modules[name, bucket] \
        == jax.jit(batcher._prefill_impl).lower(laid, *rest).as_text()


def test_stateful_serve_programs_compile_and_fit(serve_programs):
    """The `serve-cca-reason-long-out` deployment (ZAYA1-8B at its published
    widths, 16 of 40 layers, 32 slots x 2048): the decode step is given its
    latent cache AND its convolution state to keep (both aliased in to out,
    neither a temporary: what is left is the 262,272-column logits and, in
    the branches of sampling's conditionals, which a greedy step does not
    take, their scaled copy, the draw and the sort: the test below), the
    prefill of the 1024 bucket returns the state beside its rows,
    both fit the chip, and the named scopes reach the compiled program's
    text, which is where `benchmarks/scope_ops.py` reads them."""
    from benchmarks import scope_ops

    cfg, prefill, decode, cache = serve_programs("zaya1-8b-serve-d16")
    slots = cache.lengths.shape[0]
    kept = _arg_bytes((cache.k, cache.v, cache.state))
    assert cache.state.shape == (16, slots, 21, 128)  # 2,688 values
    layer = _arg_bytes((cache.k, cache.v)) / cfg.layers
    for name, program in (("prefill[1024]", prefill),
                          (f"decode[{slots}x2048]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        assert _total_bytes(program) < 16e9
        # the load [E] and the choices leave the program beside its tokens
        assert "s32[16]" in program.as_text()
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    logits = slots * cfg.vocab_size * 4
    assert m.temp_size_in_bytes < layer + 4 * logits
    assert _total_bytes(decode) < 10e9
    text = decode.as_text()
    written = re.findall(r"%(\S+) = (\S+) ([\w-]+)\(", text)
    stack = "bf16[" + ",".join(map(str, cache.k.shape)) + "]"
    assert not [n for n, shape, op in written if shape.startswith(stack)
                and "dynamic-update-slice" in n + op]
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    assert _entry_parameters(decode) == leaves + 4 + 5  # the state is one
    scopes = scope_ops.op_scopes(text)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) >= {"cca.project", "cca.conv", "cca.attend",
                           "zaya.router", "moe_experts", "lm_head", "sample"}
    # the vocabulary of scopes is written once, where the spans' is
    from ray_tpu.observability import schema

    assert set(scope_ops.SCOPES) <= set(schema.PROGRAM_SCOPES)


def test_window_serve_programs_compile_and_fit(serve_programs):
    """The `serve-window-moe-code-long-out` deployment (Laguna-S-2.1 at its
    published widths, 5 of 48 layers, 128 of 256 experts, 32 slots x 4096):
    the decode step is given BOTH kinds of rows to keep, the full layers'
    slots and the window layers' rings (both aliased in to out, neither a
    temporary, each written a row at a time), the prefill of the 2048 bucket
    returns the ring beside its rows and computes a window layer's logits in
    a band ([72, S, 2 x 512], never [72, S, S]), the held experts are read
    in place, both programs fit the chip beside each other's arguments, and
    the scopes reach the compiled text."""
    from benchmarks import harness, scope_ops

    cfg, prefill, decode, cache = serve_programs("laguna-s-2.1-serve-ep2-d5")
    slots = cache.lengths.shape[0]
    assert cache.k.shape == (2, slots, 4096, 8, 128)
    assert cache.ring_k.shape == (3, slots, 512, 8, 128)
    assert cache.state is None
    kept = _arg_bytes((cache.k, cache.v, cache.ring_k, cache.ring_v))
    assert round(kept / 1e9, 3) == 1.275  # 1.074 of slots, 0.201 of rings
    for name, program in (("prefill[2048]", prefill),
                          (f"decode[{slots}x4096]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        # the load over all 256 experts leaves the program beside its tokens
        assert "s32[256]" in program.as_text()
        # no layer's held experts (2.4 GB) are copied out of their stack
        assert not re.search(r"bf16\[128,(3072,1024|1024,3072)\]",
                             program.as_text())
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    assert m.temp_size_in_bytes < _arg_bytes((cache.k, cache.v)) / 2
    assert _total_bytes(decode) < 13.5e9
    # a prefill runs beside the engine's cache
    assert _total_bytes(prefill) + kept < 15.5e9
    text = decode.as_text()
    written = re.findall(r"%(\S+) = (\S+) ([\w-]+)\(", text)
    for stack in (cache.k, cache.ring_k):
        shape = "bf16[" + ",".join(map(str, stack.shape)) + "]"
        assert [n for n, sh, op in written if sh.startswith(shape)
                and "scatter" in n + op], shape
        assert not [n for n, sh, op in written if sh.startswith(shape)
                    and "dynamic-update-slice" in n + op], shape
    # a window layer's prefill costs S x 2 window: no [*, 2048, 2048] logits
    # of 72 heads, the band's [.., 512, 1024] instead
    ptext = prefill.as_text()
    assert re.search(r"f32\[1,4,8,9,512,1024\]", ptext)
    assert not re.search(r"f32\[1,8,9,2048,2048\]", ptext)
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    assert _entry_parameters(decode) == leaves + 5 + 5  # k, v, lengths, rings
    assert _entry_parameters(prefill) == leaves + 2
    runner = harness.load_module("runners", "serve_laguna")
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) >= {"attn.window", "attn.full", "moe.shared",
                           "moe_router", "moe_experts", "mlp", "lm_head",
                           "sample"}
    from ray_tpu.observability import schema

    assert set(runner.SCOPES) <= set(schema.PROGRAM_SCOPES)


@pytest.mark.parametrize("name", sorted(SERVE_CONFIGS))
def test_decode_sorts_the_vocabulary_only_in_a_conditionals_branch(
        serve_programs, name):
    """The chip's compiler keeps sampling's conditionals (it could have
    flattened them into selects, and every greedy step would sort again:
    16.6 of the ZAYA1 step's 30.3 ms at PR 30): the entry computation holds
    the argmax and ONE conditional under the `sample` scope, every sort over
    [slots, vocabulary] sits in a computation that is some conditional's
    branch, two conditionals deep, and the entry's own operations under
    `sample` still reach `scope_ops.op_scopes`."""
    from benchmarks import scope_ops

    cfg, _, decode, cache = serve_programs(name)
    text = decode.as_text()
    logits = f"[{cache.lengths.shape[0]},{cfg.vocab_size}]"
    holder, sorts = {}, []  # computation of every instruction; the sorts
    inside = None
    for line in text.splitlines():
        head = scope_ops._COMPUTATION.match(line)
        if head:
            inside = head[1]
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) [\w\-]+\(", line)
        if m and inside:
            holder[m[1]] = inside
            if re.search(r"[)}] sort\(", line) and logits in m[2]:
                sorts.append(m[1])
    assert len(sorts) == 1  # the top-k threshold's, nothing else's
    branch_of = {}  # branch computation -> the conditional that takes it
    for cond, branches in re.findall(
            r"%?([\w.\-]+) = .* conditional\(.*branch_computations=\{([^}]*)\}",
            text):
        for b in branches.split(","):
            branch_of[b.strip().lstrip("%")] = cond
    entry = text.split("\nENTRY ", 1)[1].split(" ", 1)[0].lstrip("%")
    inner = branch_of[holder[sorts[0]]]  # KeyError: the sort is in no branch
    outer = branch_of[holder[inner]]
    assert holder[outer] == entry
    sampled = scope_ops.op_scopes(text, ("sample",))["sample"]
    assert {inner, outer, sorts[0]} <= set(sampled)
    in_entry = [op for op in sampled if holder.get(op) == entry]
    assert outer in in_entry and len(in_entry) > 1  # the argmax beside it


def test_sparse_serve_programs_compile_and_fit(serve_programs):
    """The `serve-moe-doc-batch` deployment (OLMoE-1B-7B at its published
    widths, 8 of 16 layers, 16 slots x 2048): the 2048-bucket prefill with
    16,384 routed rows and the decode step with 128 compile for the chip as
    grouped matmuls (no [rows, experts, capacity] dispatch tensor, no copy
    of a layer's 805 MB of experts out of the stack) and fit its 16 GB
    beside the cache."""
    cfg, prefill, decode, _ = serve_programs("olmoe-1b-7b-serve-d8")
    rows = cfg.experts_per_token * SEQ  # what `_moe_mlp` would dispatch:
    dispatch_bytes = rows * cfg.num_experts * int(  # [rows, E, capacity] f32
        cfg.capacity_factor * rows / cfg.num_experts) * 4
    for name, program in (("prefill[2048]", prefill),
                          ("decode[16x2048]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        assert _total_bytes(program) < 16e9
        # the load [E] leaves the program beside its tokens
        assert "s32[64]" in program.as_text()
        assert m.temp_size_in_bytes < dispatch_bytes / 2
        assert not re.search(r"bf16\[64,(2048,1024|1024,2048)\]",
                             program.as_text())


# the five decode shapes of the serve configurations: stack layers, slots,
# rows a slot, KV heads, query heads (Mistral, OLMoE, ZAYA1, Laguna's full
# layers and its ring); head_dim 128 everywhere
DECODE_SHAPES = {
    "16x2048x8-group-of-4": (16, 16, 2048, 8, 32),
    "16x2048x16-group-of-1": (8, 16, 2048, 16, 16),
    "32x2048x2-group-of-4": (16, 32, 2048, 2, 8),
    "32x4096x8-group-of-6": (2, 32, 4096, 8, 48),
    "ring-32x512x8-group-of-9": (3, 32, 512, 8, 72),
}


@pytest.mark.parametrize("shape", sorted(DECODE_SHAPES))
def test_decode_attention_compiles(topo, shape):
    """The decode kernel at the serve cells' shapes: the stacks go in as XLA
    keeps them (tiled over KV heads x head_dim: a `[N, B, T, kvH * D]` view
    would be a copy of the whole stack), nothing of a layer's size comes
    out, and the program around the one Mosaic call holds no temporary."""
    n, b, t, kvh, h = DECODE_SHAPES[shape]
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    stack = arr((n, b, t, kvh, 128))
    compiled = jax.jit(A.decode_attention).lower(
        arr((b, h, 128)), stack, stack, arr((), jnp.int32),
        arr((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * b * h * 128 * 4
    assert f"[{n},{b},{t},{kvh * 128}]" not in text


def _results(text):
    """(name, dtype, dims, op) of every instruction of a compiled program,
    those inside fused computations among them."""
    for name, dtype, dims, op in re.findall(
            r"%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", text):
        yield name, dtype, [int(d) for d in dims.split(",") if d], op


def _prefill_passes_row_tiles(text, scopes, layer_stack=None) -> set:
    """What a prefill program whose grouped matmuls take the kernel of row
    tiles (`ops/grouped_matmul.py::_tiles_kernel`, PR 63) holds: they are
    Mosaic calls that compiled for the described chip, `ragged_dot_gated_
    tiles` and `ragged_dot_rows_tiles` (a family without a gate: the second
    alone), each under a name the benchmark's readers select the expert
    operations by (`moe_cost.EXPERT_OP`: `moe_expert_ms_per_prefill` and
    `moe_experts_roofline` read the same work) and under `moe_experts`; no
    `lax.ragged_dot` is left, and no instruction's result has one layer's
    expert stack's size (`layer_stack` = held experts, hidden, expert width:
    the stacks are read where they lie). -> the calls' names."""
    from benchmarks import moe_cost, scope_ops

    called = {scope_ops._INSTRUCTION.match(line)[1]
              for line in text.splitlines()
              if "tpu_custom_call" in line and "%ragged_dot" in line}
    assert called and all(moe_cost.EXPERT_OP.search(op) for op in called)
    assert {op.split(".")[0] for op in called} <= {
        "ragged_dot_gated_tiles", "ragged_dot_rows_tiles"}, called
    assert "ragged_dot_rows_tiles" in {op.split(".")[0] for op in called}
    assert called <= set(scopes["moe_experts"])
    assert "ragged-dot" not in text
    if layer_stack:
        held, k, n = layer_stack
        for op_name, dtype, dims, op in _results(text):
            assert not (math.prod(dims) == held * k * n
                        and sorted(dims[-2:]) == sorted((k, n))), (
                op_name, dims, op)
    return called


def _chunk_scan_calls(text) -> set:
    """The prefill's delta-rule kernels in a compiled text; no `while` is
    left under the recurrence's scope (PR 68: `kda_chunks` was a scan of XLA
    operations, 1,536 steps a prefill of 8,192 positions)."""
    from benchmarks import scope_ops

    lines = text.splitlines()
    assert not [line for line in lines if "kda.prefill_scan" in line
                and re.search(r"\bwhile\(", line)]
    return {scope_ops._INSTRUCTION.match(line)[1] for line in lines
            if "tpu_custom_call" in line and "%kda_chunk_scan" in line}


@pytest.mark.parametrize("positions, heads", [(2048, 32), (8192, 64)])
def test_chunk_scan_compiles(topo, positions, heads):
    """The delta-rule prefill kernel at both cells' shapes (Kimi-Linear's
    2,048 bucket at 32 heads, Solar-Open2's 8,192 at 64; keys and values of
    128): ONE Mosaic call within the fast memory a call is given unasked (16
    MiB: a grid step holds four heads' states and a chunk of each operand
    twice over, 1.5 MiB), and around it no more than the operands laid [B,
    H, S, D] and the output laid back."""
    from ray_tpu.ops import delta_rule

    one = SingleDeviceSharding(topo.devices[0])

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    rows = arr(1, positions, heads, 128)
    compiled = jax.jit(delta_rule.chunk_scan).lower(
        arr(1, heads, 128, 128), rows, rows, rows, rows,
        arr(1, positions, heads)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "kda_chunk_scan" in text
    assert not re.search(r"\bwhile\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 6 * positions * heads * 128 * 4


def test_linear_and_latent_serve_programs_compile_and_fit(serve_programs):
    """The `serve-kda-mla-rollout-long-out` deployment (Kimi-Linear at its
    published widths and depth, 16 of 256 experts, 32 slots x 4096): the
    decode step is given the matrix states, the convolution windows and the
    latent rows to keep (all aliased in to out), holds no temporary of the
    state stack's or of a latent layer's size, reads the latent rows with
    the Mosaic call that is given the stack, under `mla.attend`; the prefill
    of the 2048 bucket runs the recurrence in chunks and attends its latent
    layers' expanded fresh rows with the flash forward (a period's one
    latent layer and the last layer: two Mosaic calls of three inputs under
    `mla.attend`, no float32 [32, 2048, 2048] logits); both fit the chip
    beside each other's arguments, and the scopes reach the compiled text."""
    from benchmarks import harness, scope_ops

    cfg, prefill, decode, cache = serve_programs(KIMI_LINEAR)
    slots = cache.lengths.shape[0]
    assert cfg.kinds.count("kda") == 20 and cfg.kinds.count("mla") == 7
    assert cfg.kinds[0] == "kda" and cfg.kinds[-2:] == ("kda", "mla")
    assert cache.k.shape[0] == 0 and cache.state is None
    assert cache.mat.shape == (20, slots, 32, 128, 128)
    assert cache.mat.dtype == jnp.float32
    assert cache.conv.shape == (20, slots, 3 * 3 * 32 * 128)
    assert cache.latent.shape == (7, slots, 4096, 640)
    kept = _arg_bytes((cache.mat, cache.conv, cache.latent))
    assert round(_arg_bytes(cache.mat) / 1e9, 2) == 1.34
    assert round(_arg_bytes(cache.latent) / 1e9, 2) == 1.17  # 1.06 of values
    for name, program in (("prefill[2048]", prefill),
                          (f"decode[{slots}x4096]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        assert "s32[256]" in program.as_text()  # the load over all experts
        # no layer's held experts are copied out of their stack
        assert not re.search(r"bf16\[16,(2304,1024|1024,2304)\]",
                             program.as_text())
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    # no copy of the state stack (1.34 GB), of a layer of it for every slot
    # twice over, or of a latent layer (168 MB)
    assert m.temp_size_in_bytes < _arg_bytes(cache.latent) / 7
    assert _total_bytes(decode) < 12.5e9
    assert _total_bytes(prefill) + kept < 15.5e9  # beside the engine's cache
    text = decode.as_text()
    latent_layer = math.prod(cache.latent.shape[1:])
    for op_name, dtype, dims, op in _results(text):
        assert math.prod(dims) != latent_layer, (op_name, dims, op)
        assert not (dtype == "f32" and 4096 in dims
                    and math.prod(dims) >= slots * 32 * 4096), (op_name, dims)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line
             and "%latent_decode_attention" in line]
    assert len(calls) == 2  # a period's one latent layer, and the last layer
    runner = harness.load_module("runners", "serve_kimi_linear")
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) >= set(runner.SCOPES) - {"kda.prefill_scan"}
    called = {scope_ops._INSTRUCTION.match(line)[1] for line in calls}
    assert called <= set(scopes["mla.attend"])
    ptext = prefill.as_text()
    pscopes = scope_ops.op_scopes(ptext, runner.SCOPES)
    assert "kda.prefill_scan" in pscopes and "kda.state" not in pscopes
    # 512 MiB of float32 scores a latent layer went through HBM before PR 61
    for op_name, dtype, dims, op in _results(ptext):
        assert not (dtype == "f32" and dims.count(2048) >= 2
                    and math.prod(dims) >= 32 * 2048 * 2048), (op_name, dims)
    flash = _flash_forward_calls(ptext)
    assert len(flash) == 2, flash  # a period's latent layer, and the last
    assert set(flash) <= set(pscopes["mla.attend"])
    # the recurrence is the kernel, a call a kda layer of the text (the
    # lead, a period's three, the tail's one), and no loop of XLA operations
    scans = _chunk_scan_calls(ptext)
    assert len(scans) == 5 and scans <= set(pscopes["kda.prefill_scan"])
    assert serve_programs.kda_paths(KIMI_LINEAR) == {
        "decode": "state:kernel", "prefill_2048": "scan:kernel"}
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    assert _entry_parameters(decode) == leaves + 5 + 6  # k, v, lengths + 3
    from ray_tpu.observability import schema

    assert set(runner.SCOPES) <= set(schema.PROGRAM_SCOPES)


def test_delta_rule_beside_kv_rows_serve_programs_compile_and_fit(
        serve_programs):
    """The `serve-kda-gqa-docqa-8k-in-512-out` deployment (Solar-Open2 at its
    published widths, layers 0-7, 20 of 320 experts, 16 slots x 8704): the
    decode step is given the K/V rows, the matrix states of 64 heads and the
    convolution windows to keep (all aliased in to out) and holds no
    temporary of one layer's states' size; ONE scanned body of four layers:
    three state updates with the Mosaic call that is given the stack (64
    heads in one grid step: the chip's compiler takes its 32 MiB of fast
    memory), under `kda.state`, one `decode_attention` under `gqa.attend`
    and four layers' grouped matmuls; the prefill of the 8192 bucket attends
    with the flash forward and passes its rows by the experts in tiles; both
    fit the chip beside the cache, and the new scopes reach the compiled
    text."""
    from benchmarks import harness, moe_cost, scope_ops
    from ray_tpu.models import pattern

    cfg, prefill, decode, cache = serve_programs(SOLAR)
    slots = cache.lengths.shape[0]
    assert pattern.cut(cfg) == [(("gkv", "kda", "kda", "kda"), 2, True)]
    assert cfg.sparse_layers == 8 and cfg.num_params() == 3898793600
    assert cache.k.shape == (2, slots, 8704, 8, 128) and cache.latent is None
    assert cache.mat.shape == (6, slots, 64, 128, 128)
    assert cache.mat.dtype == jnp.float32
    assert cache.conv.shape == (6, slots, 3 * 3 * 64 * 128)
    kept = _arg_bytes((cache.k, cache.v, cache.mat, cache.conv))
    assert round(kept / 1e9, 2) == 1.56
    for name, program in (("prefill[8192]", prefill),
                          (f"decode[{slots}x8704]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        assert "s32[320]" in program.as_text()  # the load over all experts
        # no layer's held experts are copied out of their stack
        assert not re.search(r"bf16\[20,(4096,1280|1280,4096)\]",
                             program.as_text())
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    assert _total_bytes(decode) < 10e9
    # no copy of the state stack (0.40 GB) or of one layer of it (67 MB)
    assert m.temp_size_in_bytes < _arg_bytes(cache.mat) / 6
    assert _total_bytes(prefill) + kept < 15.5e9  # beside the engine's cache
    text = decode.as_text()
    layer_states = math.prod(cache.mat.shape[1:])
    for op_name, dtype, dims, op in _results(text):
        assert not (dtype == "f32" and math.prod(dims) == layer_states), (
            op_name, dims, op)
    runner = harness.load_module("runners", "serve_solar_open2")
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) >= set(runner.SCOPES) - {"kda.prefill_scan"}

    def mosaic(name, text=text):
        return {scope_ops._INSTRUCTION.match(line)[1]
                for line in text.splitlines()
                if "tpu_custom_call" in line and "%" + name in line}

    assert len(mosaic("kda_state_update")) == 3  # the body's three kda layers
    assert mosaic("kda_state_update") <= set(scopes["kda.state"])
    assert len(mosaic("decode_attention")) == 1  # its one gkv layer
    assert mosaic("decode_attention") <= set(scopes["gqa.attend"])
    experts = mosaic("ragged_dot")
    assert all(moe_cost.EXPERT_OP.search(op) for op in experts)
    assert experts <= set(scopes["moe_experts"]) and "ragged-dot" not in text
    assert serve_programs.grouped_paths[SOLAR] == {
        "decode": "kernel", "prefill_8192": "row_tiles"}
    assert serve_programs.attention_paths(SOLAR) == {"prefill_8192": "flash"}
    ptext = prefill.as_text()
    pscopes = scope_ops.op_scopes(ptext, runner.SCOPES)
    assert set(pscopes) >= set(runner.SCOPES) - {"kda.state", "sample"}
    assert len(mosaic("flash_attention_fwd", ptext)) == 1
    assert not mosaic("kda_state_update", ptext)
    scans = _chunk_scan_calls(ptext)  # the body's three kda layers
    assert len(scans) == 3 and scans <= set(pscopes["kda.prefill_scan"])
    assert serve_programs.kda_paths(SOLAR) == {
        "decode": "state:kernel", "prefill_8192": "scan:kernel"}


def test_state_space_serve_programs_compile_and_fit(serve_programs):
    """The `serve-ssm-lmoe-reason-long-out` deployment (Nemotron-H at its
    published widths, the first 22 layers, 64 of 512 experts, 32 slots x
    2048): the decode step is given the states, the convolution windows and
    the K/V rows to keep (all aliased in to out), holds no temporary of one
    layer's states' size, rewrites the states with the Mosaic call that is
    given the stack, under `ssm.state`, one a layer body (three scans over
    the `M E` runs between the attentions and four single layers: 4 bodies
    with a mixer, 4 with experts); its experts are TWO ungated grouped
    matmuls a body, the kernel's; the prefill of the 512 bucket runs the
    recurrence in chunks of 128; both fit the chip under 14 GB, and the
    scopes reach the compiled text."""
    from benchmarks import harness, moe_cost, scope_ops
    from ray_tpu.models import nemotron_h

    cfg, prefill, decode, cache = serve_programs(NEMOTRON_H)
    slots = cache.lengths.shape[0]
    assert [cfg.kinds.count(k) for k in ("ssm", "gqa", "lmoe")] == [10, 2, 10]
    assert [(len(u), r) for u, r in nemotron_h.runs(cfg.kinds)] == [
        (2, 3), (1, 1), (1, 1), (2, 4), (1, 1), (2, 2), (1, 1)]
    assert cfg.sparse_layers == 10 and cfg.num_params() == 5370454784
    assert cache.k.shape == (2, slots, 2048, 2, 128) and cache.state is None
    assert cache.mat.shape == (10, slots, 128, 128 * 64)
    assert cache.mat.dtype == jnp.float32
    assert cache.conv.shape == (10, slots, 3 * 10240)
    kept = _arg_bytes((cache.k, cache.v, cache.mat, cache.conv))
    assert round(_arg_bytes(cache.mat) / 1e9, 2) == 1.34
    for name, program in (("prefill[512]", prefill),
                          (f"decode[{slots}x2048]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        assert "s32[512]" in program.as_text()  # the load over all experts
        # no layer's held experts are copied out of their stack
        assert not re.search(r"bf16\[64,(1024,2688|2688,1024)\]",
                             program.as_text())
        assert _total_bytes(program) < 14e9
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    # no copy of the state stack (1.34 GB) or of one layer of it (134 MB)
    assert m.temp_size_in_bytes < _arg_bytes(cache.mat) / 10
    assert _total_bytes(prefill) + kept < 14e9  # beside the engine's cache
    text = decode.as_text()
    layer_states = math.prod(cache.mat.shape[1:])
    for op_name, dtype, dims, op in _results(text):
        assert not (dtype == "f32" and math.prod(dims) == layer_states), (
            op_name, dims, op)  # (the K stack has as many bfloat16 values)
        assert math.prod(dims) != 64 * 1024 * 2688, (op_name, dims, op)
    runner = harness.load_module("runners", "serve_nemotron_h")
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) >= set(runner.SCOPES) - {"ssm.prefill_scan"}

    def mosaic(name):
        return {scope_ops._INSTRUCTION.match(line)[1]
                for line in text.splitlines()
                if "tpu_custom_call" in line and "%" + name in line}

    assert len(mosaic("ssm_state_update")) == 4  # a body with a mixer
    assert mosaic("ssm_state_update") <= set(scopes["ssm.state"])
    experts = mosaic("ragged_dot")
    assert len(experts) == 2 * 4  # up and down, a body with experts
    assert {op.split(".")[0] for op in experts} == {"ragged_dot_rows"}
    assert all(moe_cost.EXPERT_OP.search(op) for op in experts)
    assert experts <= set(scopes["moe_experts"]) and "ragged-dot" not in text
    assert len(mosaic("decode_attention")) == 2  # the two attention layers
    # the 512 bucket's 11,264 rows over 64 held groups (22 a group, most in
    # none) pass the matrices a tile at a time: up and down, no gate
    assert serve_programs.grouped_paths[NEMOTRON_H] == {
        "decode": "kernel", "prefill_512": "row_tiles"}
    assert serve_programs.attention_paths(NEMOTRON_H) == {
        "prefill_512": "dense"}
    pscopes = scope_ops.op_scopes(prefill.as_text(), runner.SCOPES)
    tiles = _prefill_passes_row_tiles(prefill.as_text(), pscopes,
                                      (64, 1024, 2688))
    assert len(tiles) == 2 * 4  # up and down, a body with experts
    assert "ssm.prefill_scan" in pscopes and "ssm.state" not in pscopes
    # a chunk's masked decay matrix [128, 128] a head inside the scan
    assert re.search(r"f32\[128,128,8,16\]", prefill.as_text())
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    assert _entry_parameters(decode) == leaves + 5 + 5  # k, v, lengths + 2
    from ray_tpu.observability import schema

    assert set(runner.SCOPES) <= set(schema.PROGRAM_SCOPES)


def test_looped_serve_programs_compile_and_fit(serve_programs):
    """The `serve-loop4-mha-problems-256-in-256-out` deployment (Ouro-2.6B
    whole: 48 layers run four times a token, the whole vocabulary, 8 slots x
    512): the decode step is given K/V stacks of 4 x 48 = 192 layers to keep
    (6.44 GB aliased in to out) and reads the rows of cache layer t * 48 + i
    with `decode_attention`; the stacked weights are read where they lie by
    every pass: ONE layer body (one Mosaic call: neither loop is unrolled)
    and no array of [4, 48, ...] or [192, ...] weights; the three stacks
    `wq`, `wk`, `wv` lie on the chip tiled by head, as the step's products
    read them (PR 66: the decode program is compiled with `Layout.AUTO` for
    its weights; in the default tiling the compiler copied all three into
    that one in ENTRY, 1.2 GB every step), so its temporaries are less than
    one cache layer (16.8 MB) and 8 MB. The 256 bucket's prefill, compiled
    for the decode step's choice, keeps its scores dense (4 MiB a call:
    under `DENSE_SCORES_BYTES`, the one rule every configuration's prefill
    reads) and copies two of the stacks where it copied three (it wants
    another tiling of them; 1.0 GB of temporaries for 1.4), and both fit
    the chip."""
    from benchmarks import harness, scope_ops
    from ray_tpu.observability import schema
    from ray_tpu.ops import attention as A

    cfg, prefill, decode, cache = serve_programs(OURO)
    assert cfg.num_params() == 2_667_974_657 and cfg.sparse_layers == 0
    assert cfg.loop_steps == 4 and cfg.sandwich and cfg.full_layers == 192
    assert cache.k.shape == cache.v.shape == (192, 8, 512, 16, 128)
    assert cache.k.dtype == jnp.bfloat16 and cache.state is None
    assert A.decode_attention_takes(cache.k, cache.v)
    batcher = serve_programs.engine(OURO)[0]
    assert batcher.decode_attention_path == {"decode": "kernel"}
    assert serve_programs.attention_paths(OURO) == {"prefill_256": "dense"}
    kept, a_layer = _arg_bytes((cache.k, cache.v)), _arg_bytes(cache.k) // 192
    assert round(kept / 1e9, 2) == 6.44 and a_layer == 16_777_216
    for name, program in (("prefill[256]", prefill), ("decode[8x512]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    assert m.temp_size_in_bytes < a_layer + 8e6
    assert _total_bytes(decode) < 12e9
    assert _total_bytes(prefill) + kept < 13.5e9  # beside the engine's cache
    text = decode.as_text()
    entry = text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    assert not [line for line in entry.splitlines()
                if " copy(" in line and "bf16[48,2048,16,128]" in line]
    relaid = {jax.tree_util.keystr(path, simple=True, separator="/"):
              chosen.layout.major_to_minor
              for path, chosen in jax.tree_util.tree_leaves_with_path(
                  batcher._formats) if chosen.layout is not None
              and chosen.layout.major_to_minor != tuple(range(len(
                  chosen.layout.major_to_minor)))}
    assert relaid == {f"blocks/{w}": (0, 2, 1, 3) for w in ("wq", "wk", "wv")}
    for op_name, dtype, dims, op in _results(text):
        assert dims[:2] != [4, 48] and not (
            dims[:1] == [192] and dims[1:] != [8, 512, 16, 128]), (
                op_name, dims, op)
    attends = [op for op, _ in _pallas_calls(text)]
    assert len(attends) == 1 and attends[0].startswith("decode_attention")
    assert not _pallas_calls(prefill.as_text())
    runner = harness.load_module("runners", "serve_ouro")
    assert set(runner.SCOPES) <= set(schema.PROGRAM_SCOPES)
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) == set(runner.SCOPES)
    assert attends[0] in scopes["attend_cached"]
    assert set(scope_ops.op_scopes(prefill.as_text(), runner.SCOPES)) \
        >= set(runner.SCOPES) - {"sample"}
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    # a served program returns no `exit_pdf`: the gate's two leaves are no
    # operand of either (at the threshold 1 no logit reads them)
    assert _entry_parameters(decode) == leaves - 2 + 3 + 5
    assert _entry_parameters(prefill) == leaves - 2 + 2


def test_mamba1_serve_programs_compile_and_take_their_kernels(serve_programs):
    """The `serve-mamba1-mqa-docreason-8k-in-long-out` deployment (Jamba2-3B
    whole: 28 layers, the whole vocabulary, 16 slots x 12288): all THREE
    kernels take the benchmark configuration's shapes, so that a later change
    cannot send this cell to a fallback unseen: the decode step rewrites the
    26 mixers' states with the Mosaic call that is given the stack, under
    `ssm1.state`, one a scanned body, and reads the ONE bfloat16 KV head's rows
    with `decode_attention`; states, windows and rows are aliased in to out
    and no temporary of a layer's states' size is held; the prefill of the
    8192 bucket scans with `selective_scan` under `ssm1.prefill_scan`, holds
    no [8192, 16, 5120] array and no [*, 8192, 8192] logits (the flash
    forward, 20 heads on 1), and both fit the chip beside each other."""
    from benchmarks import harness, scope_ops
    from ray_tpu.models import nemotron_h
    from ray_tpu.ops import attention as A, ssd

    cfg, prefill, decode, cache = serve_programs(JAMBA)
    slots = cache.lengths.shape[0]
    assert [cfg.kinds.count(k) for k in ("ssm1", "gqa", "mlp")] == [26, 2, 28]
    assert [(len(u), r) for u, r in nemotron_h.runs(cfg.kinds)] == [
        (2, 7), (1, 1), (2, 13), (1, 1), (1, 1), (2, 6), (1, 1)]
    assert cfg.num_params() == 3_029_337_472 and cfg.sparse_layers == 0
    assert cache.k.shape == (2, slots, 12288, 1, 128)
    assert cache.k.dtype == jnp.bfloat16 and cache.state is None
    assert cache.mat.shape == (26, slots, 16, 5120)
    assert cache.mat.dtype == jnp.float32
    assert cache.conv.shape == (26, slots, 3 * 5120)
    # each kernel's `takes`, on the configuration's own shapes
    assert A.decode_attention_takes(cache.k, cache.v)
    assert ssd.ssm_state_update_takes(cache.mat)
    assert ssd.selective_scan_takes(
        jax.ShapeDtypeStruct((1, 16, 5120), jnp.float32), 8192)
    row = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)
    assert A.flash_attention_takes(
        jax.ShapeDtypeStruct((1, 8192, 20, 128), jnp.bfloat16), row, row)
    batcher_paths = serve_programs.engine(JAMBA)[0]
    assert batcher_paths.ssm_path == {"prefill_8192": "scan:kernel",
                                      "decode": "state:kernel"}
    assert batcher_paths.decode_attention_path == {"decode": "kernel"}
    assert serve_programs.attention_paths(JAMBA) == {"prefill_8192": "flash"}
    kept = _arg_bytes((cache.k, cache.v, cache.mat, cache.conv))
    assert round(kept / 1e9, 2) == 0.35
    for name, program in (("prefill[8192]", prefill),
                          (f"decode[{slots}x12288]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    assert m.temp_size_in_bytes < _arg_bytes(cache.mat) / 4
    assert _total_bytes(decode) < 7e9
    assert _total_bytes(prefill) + kept < 9e9  # beside the engine's cache
    text, ptext = decode.as_text(), prefill.as_text()
    for op_name, dtype, dims, op in _results(ptext):
        # no [heads, S, S] logits (an MLP's [S, 8192] is no such array)
        assert not (dims.count(8192) >= 2 and len(dims) > 2), (
            op_name, dims, op)
        assert not (8192 in dims and 16 in dims and 5120 in dims), (
            op_name, dims, op)  # no [S, state, channels] array

    def mosaic(of, name):
        return {scope_ops._INSTRUCTION.match(line)[1]
                for line in of.splitlines()
                if "tpu_custom_call" in line and "%" + name in line}

    runner = harness.load_module("runners", "serve_jamba")
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    # (a step's gate is one multiply, fused into the output projection's)
    assert set(scopes) >= set(runner.SCOPES) - {"ssm1.prefill_scan",
                                                "ssm1.gate"}
    updates = mosaic(text, "selective_state_update")
    assert len(updates) == 3  # the three scanned bodies: 7 + 13 + 6 mixers
    assert updates <= set(scopes["ssm1.state"])
    attends = mosaic(text, "decode_attention")
    assert len(attends) == 2 and attends <= set(scopes["attn.gqa"])
    pscopes = scope_ops.op_scopes(ptext, runner.SCOPES)
    scans = mosaic(ptext, "selective_scan")
    # (the compiler fuses the call's producers, B and C spread over the
    # lanes and the state's read, INTO it: the program's operation is that
    # fusion, under the kernel's name and scope, the call inside it)
    assert len(scans) == 3 and sum(op.startswith("selective_scan") for op in
                                   pscopes["ssm1.prefill_scan"]) == 3
    assert "ssm1.state" not in pscopes
    flash = mosaic(ptext, "flash_attention_fwd")
    assert len(flash) == 2 and flash <= set(pscopes["attn.gqa"])
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    assert _entry_parameters(decode) == leaves + 5 + 5  # k, v, lengths + 2
    from ray_tpu.observability import schema

    assert set(runner.SCOPES) <= set(schema.PROGRAM_SCOPES)


def test_sink_window_serve_programs_compile_and_fit(serve_programs):
    """The `serve-sink-window-moe-agent-8k-in-2k-out` deployment
    (MiMo-V2-Flash at its published widths, layers 0-10, 16 of 256 experts, 32
    slots x 10240): the cache holds each kind's rows by its own KV heads, a
    key of 192 as two pieces of 128 beside a value of 128; the decode step
    is given all four stacks to keep (aliased in to out), keeps no window
    layer's rows in slots, and reads both kinds' rows with the Mosaic call
    that is given the stacks (a lead, a run of 4 window layers, a full layer,
    a run of 5: four bodies, three scans); the prefill of the 8192 bucket
    attends its full layers' fresh rows with the two-width flash forward and
    holds no [*, 8192, 8192] array, and stays with the engine's cache under
    15 GB."""
    from benchmarks import harness, scope_ops
    from ray_tpu.models import laguna, pattern

    cfg, prefill, decode, cache = serve_programs(MIMO)
    slots = cache.lengths.shape[0]
    assert [cfg.kinds.count(k) for k in ("full", "window")] == [2, 9]
    assert [(len(u), r) for u, r in pattern.runs(
        cfg.layer_kinds[1:], laguna.RUN_MAX)] == [(1, 4), (1, 1), (1, 5)]
    assert cfg.sparse_layers == 10 and cfg.num_params() == 5422283840
    assert cache.k.shape == (2, slots, 10240, 8, 128)  # 4 KV heads x 2 pieces
    assert cache.v.shape == (2, slots, 10240, 4, 128)
    assert cache.ring_k.shape == (9, slots, 128, 16, 128)  # 8 KV heads x 2
    assert cache.ring_v.shape == (9, slots, 128, 8, 128)
    assert cache.state is None and cache.mat is None
    kept = _arg_bytes((cache.k, cache.v, cache.ring_k, cache.ring_v))
    assert round(kept / 1e9, 2) == 2.24  # 2.013 of slots, 0.226 of rings
    for name, program in (("prefill[8192]", prefill),
                          (f"decode[{slots}x10240]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        assert "s32[256]" in program.as_text()  # the load over all experts
        # no layer's held experts are copied out of their stack
        assert not re.search(r"bf16\[16,(4096,2048|2048,4096)\]",
                             program.as_text())
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    assert m.temp_size_in_bytes < _arg_bytes(cache.k) / 4  # no layer of it
    assert _total_bytes(decode) < 15e9
    assert _total_bytes(prefill) + kept < 15e9  # beside the engine's cache
    text, ptext = decode.as_text(), prefill.as_text()
    for op_name, dtype, dims, op in _results(ptext):
        assert dims.count(8192) < 2, (op_name, dims, op)  # no [S, S] logits
    for op_name, dtype, dims, op in _results(text):
        # no window layer keeps its rows in slots: nine layers of them
        assert not (dims[:1] == [9] and 10240 in dims), (op_name, dims, op)

    def mosaic(of, name):
        return {scope_ops._INSTRUCTION.match(line)[1]
                for line in of.splitlines()
                if "tpu_custom_call" in line and "%" + name in line}

    runner = harness.load_module("runners", "serve_mimo")
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) >= set(runner.SCOPES)
    attends = mosaic(text, "decode_attention")
    assert len(attends) == 4  # the lead, a window body, the full, a window
    assert len(attends & set(scopes["attn.full"])) == 2
    assert len(attends & set(scopes["attn.window"])) == 2
    flash = mosaic(ptext, "flash_attention_fwd")
    pscopes = scope_ops.op_scopes(ptext, runner.SCOPES)
    assert len(flash) == 2 and flash <= set(pscopes["attn.full"])
    assert serve_programs.attention_paths(MIMO) == {"prefill_8192": "flash"}
    # the capped call's 8,192 rows and the whole layout's 32,768 over 16
    # held groups both pass the matrices a tile at a time
    assert serve_programs.grouped_paths[MIMO] == {
        "decode": "kernel", "prefill_8192": "row_tiles"}
    _prefill_passes_row_tiles(ptext, pscopes, (16, 4096, 2048))
    # a window layer's prefill in a band: [.., 128, 256] logits
    assert re.search(r"f32\[1,64,8,8,128,256\]", ptext)
    from ray_tpu.observability import schema

    assert set(runner.SCOPES) <= set(schema.PROGRAM_SCOPES)


def test_longcat_serve_programs_compile(serve_programs):
    """LongCat-Flash's programs at the published widths, 4 double layers: the
    decode step is given the latent rows of all 8 attention SUBLAYERS to keep
    (aliased in to out), holds no temporary of a sublayer's size and no
    float32 logits over `max_len`, reads the rows with the Mosaic call that
    is given the stack, twice a scanned double layer, under `mla.attend`;
    the prefill of the 4096 bucket attends its expanded fresh rows with the
    flash forward (keys of 192 in 256 lanes beside values of 128), one Mosaic
    call of three inputs a scanned sublayer under `mla.attend`, and holds no
    float32 logits at all, neither [64, 4096, 4096] nor the [64, 512, 4096]
    blocks it went by before PR 61, and no [4096 x 12, 6144] expert rows
    (1024 rows a call); both fit the chip, the prefill beside the engine's
    cache, and the scopes reach the compiled text."""
    from benchmarks import harness, scope_ops

    cfg, prefill, decode, cache = serve_programs(LONGCAT)
    slots = cache.lengths.shape[0]
    assert cfg.kinds == ("scmoe",) * 4 and cfg.latent_layers == 8
    assert cache.k.shape[0] == 0 and cache.state is None and cache.mat is None
    assert cache.latent.shape == (8, slots, 5120, 640)
    kept = _arg_bytes(cache.latent)
    assert round(kept / 1e9, 2) == 1.68  # 1.51 of values
    for name, program in (("prefill[4096]", prefill),
                          (f"decode[{slots}x5120]", decode)):
        m = program.memory_analysis()
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} + "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} + temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} - aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} = "
              f"{_total_bytes(program) / 1e9:.2f} GB")
        assert "s32[768]" in program.as_text()  # the load over all outputs
        # no layer's held experts are copied out of their stack
        assert not re.search(r"bf16\[16,(6144,2048|2048,6144)\]",
                             program.as_text())
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= kept
    # under one sublayer (0.14 GB, my compile, PR 44; a sublayer is 0.21): no
    # copy of the stack, and none of a double layer's part of a parameter
    # stack (0.30 GB a dense matrix's [2, ...] when the sublayer was sliced
    # out in two steps: half the step on the chip)
    assert m.temp_size_in_bytes < kept / 8
    assert _total_bytes(decode) < 14e9
    assert _total_bytes(prefill) + kept < 15.5e9  # beside the engine's cache
    text, ptext = decode.as_text(), prefill.as_text()
    sublayer = math.prod(cache.latent.shape[1:])
    for op_name, dtype, dims, op in _results(text):
        assert math.prod(dims) != sublayer, (op_name, dims, op)
        assert not (dtype == "f32" and 5120 in dims
                    and math.prod(dims) >= slots * 64 * 5120), (op_name, dims)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line
             and "%latent_decode_attention" in line]
    assert len(calls) == 2  # a double layer's two sublayers, scanned
    runner = harness.load_module("runners", "serve_longcat")
    scopes = scope_ops.op_scopes(text, runner.SCOPES)
    print({k: len(v) for k, v in scopes.items()})
    assert set(scopes) == set(runner.SCOPES)
    called = {scope_ops._INSTRUCTION.match(line)[1] for line in calls}
    assert called <= set(scopes["mla.attend"])
    pscopes = scope_ops.op_scopes(ptext, runner.SCOPES)
    assert set(pscopes) >= set(runner.SCOPES) - {"sample"}
    # no float32 logits through HBM: neither S x S nor a block of 512
    # queries against the keys up to its end (150 of a prefill's 331 ms on
    # the chip, PERF.md PR 44)
    assert not re.search(r"f32\[(1,)?64,4096,4096\]", ptext)
    for op_name, dtype, dims, op in _results(ptext):
        assert not (dtype == "f32" and 4096 in dims
                    and math.prod(dims) >= 64 * 512 * 4096), (op_name, dims)
    flash = _flash_forward_calls(ptext)
    assert len(flash) == 2, flash  # a double layer's two sublayers, scanned
    assert set(flash) <= set(pscopes["mla.attend"])
    # the expert layer's 1,024-row pieces (12,288 assignments, capped to
    # 1,024 rows; the whole layout beside it) pass the matrices a tile at a
    # time, gate and up in two column blocks of 1,024
    # (a step whose cap gives way, 384 rows over 16 groups, is neither
    # kernel's shape: `lax.ragged_dot`, as before PR 63)
    assert serve_programs.grouped_paths[LONGCAT] == {
        "decode": "kernel+ragged_dot", "prefill_4096": "row_tiles"}
    _prefill_passes_row_tiles(ptext, pscopes, (16, 6144, 2048))
    assert re.search(r"\[12288,6144\]", ptext)  # 1024 rows x 12 a call
    assert not re.search(r"\[49152,6144\]", ptext)
    leaves = len(jax.tree.leaves(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0)))))
    assert _entry_parameters(decode) == leaves + 5 + 4  # k, v, lengths, latent
    from ray_tpu.observability import schema

    assert set(runner.SCOPES) <= set(schema.PROGRAM_SCOPES)


@pytest.mark.parametrize("name", sorted(SERVE_CONFIGS))
def test_decode_reads_the_cache_in_its_stack_with_the_kernel(
        serve_programs, name):
    """Every decode program the engine jits (dense, sparse, stateful, layer
    pattern): attention is the Mosaic call that is given the stack, under
    the scope the benchmark's readers select by (`attend_cached`,
    `cca.attend`, `attn.full`, `attn.window`); NO instruction of the program,
    fused or not, has a result of a cache layer's size (67 / 134 / 33.5 / 268
    / 34 MB: before PR 35 a `dynamic-slice` fusion read the whole layer, or a
    `slice` copied it out), and none holds float32 logits over `max_len`
    (or the window) key positions; the cache stays aliased in to out."""
    from benchmarks import harness, scope_ops

    cfg, _, decode, cache = serve_programs(name)
    text = decode.as_text()
    slots = cache.lengths.shape[0]
    kinds = {"slots": (cache.k, cfg.heads)}
    if cache.ring_k is not None:
        kinds["ring"] = (cache.ring_k, cfg.window_heads)
    calls = [line for line in text.splitlines()  # not the `ragged-dot`s'
             if "tpu_custom_call" in line and "%decode_attention" in line]
    per_body = 1 if not cfg.layer_kinds else 1 + len(cfg.layer_kinds)
    assert len(calls) == per_body  # the leading layer's, and a period's four
    for kind, (stack, heads) in kinds.items():
        layer = math.prod(stack.shape[1:])
        t = stack.shape[2]
        for op_name, dtype, dims, op in _results(text):
            assert math.prod(dims) != layer, (kind, op_name, dims, op)
            assert not (dtype == "f32" and t in dims
                        and math.prod(dims) >= slots * heads * t), (
                kind, op_name, dims, op)
    kept = [a for a in (cache.k, cache.v, cache.state, cache.ring_k,
                        cache.ring_v) if a is not None]
    assert decode.memory_analysis().alias_size_in_bytes >= _arg_bytes(kept)
    # the scopes, as `serve_zaya.decode_op_scopes` / `serve_laguna`'s read them
    wanted, scopes = ("attend_cached",), scope_ops.SCOPES + ("attend_cached",)
    if cfg.attention == "cca":
        wanted = ("cca.attend",)
    if cfg.layer_kinds:
        wanted = ("attn.full", "attn.window")
        scopes = harness.load_module("runners", "serve_laguna").SCOPES
    by_scope = scope_ops.op_scopes(text, scopes)
    called = {scope_ops._INSTRUCTION.match(line)[1] for line in calls}
    assert called <= {op for s in wanted for op in by_scope[s]}
    for scope in wanted:
        assert called & set(by_scope[scope]), scope


@pytest.mark.parametrize("name", sorted(SERVE_CONFIGS))
def test_prefill_attends_its_fresh_rows_with_the_flash_kernel(
        serve_programs, name):
    """Every prefill program whose layers attend through `attend_held` (the
    one block's two, ZAYA1's, Laguna's full layers), at a 2,048 bucket: the
    fresh rows of a prefill from position 0 go to the flash forward kernel,
    one Mosaic call of three inputs a scanned body under the name and the
    scope the benchmark's readers select by, the engine's counter says
    "flash" for the bucket (its float32 scores [heads, S, S] are past what
    the XLA spelling keeps in fast memory, `A.DENSE_SCORES_BYTES`, over 8
    heads too), and NO instruction, fused or not, has a float32
    result of `[heads, S, S]` elements with two dimensions of the bucket's
    length: before PR 53 `_attend_cached` wrote and read such logits three
    times a layer (20 instructions of `f32[8,2048,2048,4]` in the dense
    program, 40 of `f32[1,8,6,2048,2048]` in Laguna's). A window layer's
    band stays. ZAYA1's cell prefills in the 1,024 bucket: 32 MiB of scores
    over 8 heads, which XLA keeps in fast memory, so that program is the
    parent's (below) and the counter says "dense" for it."""
    from benchmarks import harness, scope_ops

    cfg, bucket = serve_programs(name)[0], SEQ
    text = serve_programs.prefill_at(name, bucket).as_text()
    paths = serve_programs.attention_paths(name)
    assert paths[f"prefill_{bucket}"] == "flash"
    if name == "zaya1-8b-serve-d16":
        assert paths == {"prefill_1024": "dense", "prefill_2048": "flash"}
    calls = _flash_forward_calls(text)
    # one scanned body; a pattern's leading layer and its period's full one
    assert len(calls) == (1 + cfg.layer_kinds.count("full")
                          if cfg.layer_kinds else 1), calls
    logits = [(op_name, dims, op) for op_name, dtype, dims, op in _results(text)
              if dtype == "f32" and dims.count(bucket) >= 2
              and math.prod(dims) >= cfg.heads * bucket * bucket]
    assert not logits, logits[:4]
    wanted, scopes = "attend_cached", scope_ops.SCOPES + ("attend_cached",)
    if cfg.attention == "cca":
        wanted = "cca.attend"
    if cfg.layer_kinds:
        wanted = "attn.full"
        scopes = harness.load_module("runners", "serve_laguna").SCOPES
    assert set(calls) <= set(scope_ops.op_scopes(text, scopes)[wanted])


def _program_text(program) -> str:
    """A compiled program's text without what names the checkout and the
    call stack: the tables of file names and stack frames, every
    instruction's frame id, and a Mosaic call's payload (its module with the
    kernel's own source locations)."""
    text = "\n".join(
        re.sub(r"backend_config=.*", "backend_config=<payload>", line)
        if "tpu_custom_call" in line else line
        for line in program.as_text().split("\n"))
    head, _, rest = text.partition("\nFileNames\n")
    if rest:
        body = rest.partition("\nStackFrames\n")[2].split("\n\n", 1)[1]
        text = head + "\n" + body
    return re.sub(r'source_file="[^"]*"|source_line=\d+| ?stack_frame_id=\d+',
                  "", text)


# sha256 (16 digits) of `_program_text` of the programs that PR 53, which
# changed what a prefill from position 0 attends with, left as its parent
# compiled them line for line: every decode step, both programs of the two
# families whose prefill has an attention of its own, and ZAYA1's 1,024
# bucket. A PR that means to change one of these programs pins what the
# failure prints. PR 59 pinned four: Kimi-Linear's two (a sixteenth held:
# `held_rows_cap` answers, 64 of a step's 256 rows, 4,096 of a 2,048-row
# prompt's 16,384) and LongCat's two, whose capped expert layers count an
# int32 [3] now (reached, rows gathered, whole-layout calls: a handful of
# scalar adds and pads, no other instruction). A configuration without a
# cap (Laguna's half) keeps the parent's program to the character. PR 61
# sent the two latent families' PREFILLS to the flash forward
# (`LATENT_PREFILLS` below holds them to it); their decode steps stay. PR 62
# pinned Laguna's and Kimi-Linear's decode steps anew: the same instructions
# in the same order on the same operands, and other NUMBERS in their names
# (`%mul.1155` -> `%mul.1149`): the one layer loop (`pattern.forward_cached`)
# traces fewer dead index computations than each family's own loop did
# (`build/pr62/compare_texts.py` numbers the names anew and finds the texts
# equal). PR 63 pinned ZAYA1's 1,024 bucket anew: its 1,024 rows over 16
# groups pass the experts' matrices with the kernel of row tiles, as every
# sparse prefill does since (`_prefill_passes_row_tiles`); every DECODE
# step here was the one PR 62 left until PR 66 pinned ALL seven anew: the
# decode step is compiled with `Layout.AUTO` for its weights and every
# program here for what it chose (`wq` / `wk` / `wv` tiled by head, a
# router, the latent projections: PERF.md section 6, PR 66, has the leaves
# by configuration), so a projection's slice of its stack is read inside
# the product's fusion and no longer written out first; ZAYA1's prefill
# reads the decode step's choice of its four leaves.
UNCHANGED_PROGRAMS = {
    ("zaya1-8b-serve-d16", "prefill"): "d6570bfa0207db33",
    ("mistral7b-v03-serve-d16", "decode"): "eee95144c06f4bbd",
    ("olmoe-1b-7b-serve-d8", "decode"): "b0482fe40d6d6b49",
    ("zaya1-8b-serve-d16", "decode"): "8a7e6aa8169d0518",
    ("laguna-s-2.1-serve-ep2-d5", "decode"): "32174ca84a35523a",
    (KIMI_LINEAR, "decode"): "381ffe89d2e79edc",
    (LONGCAT, "decode"): "2df7d08a1ecd6aa2",
}
# a latent family's prefill -> its cell's bucket
LATENT_PREFILLS = {KIMI_LINEAR: 2048, LONGCAT: 4096}


@pytest.mark.parametrize("name,kind", sorted(UNCHANGED_PROGRAMS))
def test_programs_a_prefills_attention_does_not_reach_are_the_parents(
        serve_programs, name, kind):
    import hashlib

    _, prefill, decode, _ = serve_programs(name)
    text = _program_text(prefill if kind == "prefill" else decode)
    assert not re.search(r"flash_attention_(fwd|bwd)", text)
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == UNCHANGED_PROGRAMS[name, kind], (
        f"{name} {kind}: {len(text.splitlines())} lines now hash to {got}")


@pytest.mark.parametrize("name", sorted(LATENT_PREFILLS))
def test_a_latent_prefill_attends_with_the_flash_kernel(serve_programs, name):
    """The two prefills whose attention is `kimi_linear.mla_attention`'s own
    (until PR 61 pinned above as programs a prefill's attention does not
    reach): the engine's counter says "flash" for the cell's bucket and
    nothing else, and the flash forward is the only attention kernel of the
    program (no backward, no decode kernel)."""
    _, prefill, _, _ = serve_programs(name)
    assert serve_programs.attention_paths(name) == {
        f"prefill_{LATENT_PREFILLS[name]}": "flash"}
    kernels = {op.rstrip(".0123456789") for op, _ in _pallas_calls(
        prefill.as_text()) if "attention" in op}
    assert kernels == {"flash_attention_fwd"}


# the four sparse serve programs: what one layer's expert stack holds (the
# held experts x hidden x expert width) and the totals their own tests hold
SPARSE_PROGRAMS = {
    "olmoe-1b-7b-serve-d8": (64 * 2048 * 1024, 10e9),
    "zaya1-8b-serve-d16": (16 * 2048 * 2048, 10e9),
    "laguna-s-2.1-serve-ep2-d5": (128 * 3072 * 1024, 13.5e9),
    KIMI_LINEAR: (16 * 2304 * 1024, 12.5e9),
}


@pytest.mark.parametrize("name", sorted(SPARSE_PROGRAMS))
def test_decode_multiplies_the_experts_with_the_kernel(serve_programs, name):
    """Every sparse decode program the engine jits: the grouped matmuls are
    the Mosaic calls of `ops/grouped_matmul.py`, two a sparse layer body
    (gate and up in one, down in the other), under `moe_experts`, each under
    a name the benchmark's readers select the expert operations by; NO
    instruction has a result of one layer's expert stack's size (the stack
    is indexed where it lies: 134-805 MB a layer copied otherwise); no
    `lax.ragged_dot` is left in the step; the cache stays aliased and the
    total under what the configuration's own test holds it to (the kernel's
    buffers are fast memory, not the program's). The 2,048-token prefill's
    thousands of rows a group take the kernel of row tiles
    (`_prefill_passes_row_tiles`), and the engine's counter says both."""
    from benchmarks import moe_cost, scope_ops

    cfg, prefill, decode, cache = serve_programs(name)
    layer_stack, limit = SPARSE_PROGRAMS[name]
    text = decode.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "%ragged_dot" in line]
    # one scanned body, or a pattern's period and trailing layers unrolled
    bodies = len(cfg.layer_kinds) + len(cfg.tail_kinds) or 1
    # a thin held share (Kimi-Linear's sixteenth: 64 of a step's 256 rows)
    # compiles both branches of `moe_dropless`'s `lax.cond`, and runs one
    slots = cache.lengths.shape[0]
    capped = T.held_rows_cap(cfg, slots * cfg.experts_per_token)
    assert (capped is not None) == (name == KIMI_LINEAR)
    assert len(calls) == 2 * bodies * (2 if capped else 1)
    called = {scope_ops._INSTRUCTION.match(line)[1] for line in calls}
    assert all(moe_cost.EXPERT_OP.search(op) for op in called)
    assert {op.split(".")[0] for op in called} == {"ragged_dot_gated",
                                                   "ragged_dot_rows"}
    by_scope = scope_ops.op_scopes(text, ("moe_experts",))
    assert called <= set(by_scope["moe_experts"])
    assert "ragged-dot" not in text
    for op_name, dtype, dims, op in _results(text):
        assert math.prod(dims) != layer_stack, (op_name, dims, op)
    kept = [getattr(cache, f) for f in cache._fields
            if f != "lengths" and getattr(cache, f) is not None]
    assert decode.memory_analysis().alias_size_in_bytes >= _arg_bytes(kept)
    assert _total_bytes(decode) < limit
    # the prefill's many rows a group (16,384 over 64; ZAYA1's 1,024 over
    # 16; Laguna's 20,480 over 128 held; Kimi-Linear's capped 4,096 and whole
    # 16,384 over 16 held) pass the matrices a tile at a time
    bucket = 1024 if name.startswith("zaya") else SEQ
    assert serve_programs.grouped_paths[name] == {
        "decode": "kernel", f"prefill_{bucket}": "row_tiles"}
    ptext = prefill.as_text()
    stack = ((cfg.experts_held or (0, cfg.num_experts))[1], cfg.hidden,
             cfg.mlp_hidden)
    assert math.prod(stack) == layer_stack
    tiles = _prefill_passes_row_tiles(
        ptext, scope_ops.op_scopes(ptext, ("moe_experts",)), stack)
    assert {op.split(".")[0] for op in tiles} == {"ragged_dot_gated_tiles",
                                                  "ragged_dot_rows_tiles"}


def test_attend_cached_reads_the_cache_once(topo):
    """The serve cells' decode shape (Mistral-7B widths: 32 heads over 8 KV
    heads, 16 slots x 2048 positions), the function alone. Repeating the
    cache per query head and upcasting it cost a float32 [16,2048,8,4,128]
    temporary (537 MB) for K and one for V in every layer; the MHA widths
    above never reach that."""
    from ray_tpu.models.decoding import _attend_cached

    slots, kv_heads, hd = 16, 8, 128
    q, cache, q_pos, kv_len_mask = _on(SingleDeviceSharding(topo.devices[0]), (
        jax.ShapeDtypeStruct((slots, 1, 32, hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((slots, SEQ, kv_heads, hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        jax.ShapeDtypeStruct((slots, SEQ), jnp.bool_)))
    cache_elems = math.prod(cache.shape)
    compiled = jax.jit(_attend_cached).lower(
        q, cache, cache, q_pos, kv_len_mask).compile()
    # under one cache layer's bytes (67 MB)
    assert (compiled.memory_analysis().temp_size_in_bytes
            < cache_elems * cache.dtype.itemsize)
    float32_shapes = set(re.findall(r"f32\[([\d,]+)\]", compiled.as_text()))
    assert not [m for m in float32_shapes
                if math.prod(map(int, m.split(","))) >= cache_elems]


# bf16 keeps 8 significant bits, so a result is rounded by up to half its
# last place: 2^-6 for an output under 8, 2^-4 for a gradient under 32 (sums
# of a few hundred products of standard normal values); `p` and `ds` enter
# their products rounded to bf16 too, which these cover. The float32 cases
# keep 2e-5 / 5e-5.
BF16_ATOL = (2 ** -6, 2 ** -4)


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("causal,sq,sk,dtype,blocks,head_dim", [
    (True, 128, 128, F32, (64, 64), 32),    # block-aligned: a diagonal block a row
    (False, 128, 128, F32, (64, 64), 32),   # the body without a mask alone
    (True, 100, 100, F32, (64, 64), 32),    # padded to the block
    (False, 72, 136, F32, (64, 64), 32),    # cross-attention shape, both padded
    (True, 256, 256, F32, (64, 64), 32),    # 4 x 4 blocks: 6 bare, 4 masked, 6 skipped
    (True, 200, 200, F32, (64, 64), 32),    # the same with a padded last block
    (True, 320, 192, F32, (64, 64), 32),    # sq > sk: rows below every key
    (True, 192, 320, F32, (64, 64), 32),    # sq < sk: keys that no row sees
    (True, 136, 72, F32, (64, 64), 32),     # both ways again, both padded
    (True, 72, 136, F32, (64, 64), 32),
    (False, 136, 72, F32, (64, 64), 32),
    (True, 256, 256, BF16, (64, 64), 32),   # operands as the train cells send them
    (True, 200, 136, BF16, (64, 64), 32),
    (False, 192, 128, BF16, (64, 64), 32),
    # whole lanes, as on the chip: the row statistics' [rows, 128] layout
    # and its way to and from the rows in HBM, one and two tiles a block
    (True, 384, 384, F32, (128, 128), 128),
    (True, 512, 512, F32, (256, 256), 128),
    (False, 256, 512, F32, (128, 256), 128),
    (True, 512, 256, BF16, (256, 128), 128),
])
def test_pallas_kernels_match_reference_in_interpret_mode(
        monkeypatch, causal, sq, sk, dtype, blocks, head_dim):
    """Forward, dQ and dK/dV kernels against float32 mha_reference and its
    jax.grad on the same (rounded) inputs, through the Pallas interpreter
    on the CPU: every path of the block step at toy size."""
    import jax.experimental.pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v, g = (
        jax.random.normal(key, (1, s, 2, head_dim), jnp.float32).astype(dtype)
        for key, s in zip(ks, (sq, sk, sk, sq)))

    flash = functools.partial(A.flash_attention, causal=causal,
                              block_q=blocks[0], block_k=blocks[1])
    ref = functools.partial(A.mha_reference, causal=causal)
    out, vjp = jax.vjp(flash, q, k, v)
    q32, k32, v32, g32 = (x.astype(jnp.float32) for x in (q, k, v, g))
    want_out, want_vjp = jax.vjp(ref, q32, k32, v32)
    atol_out, atol_grad = (2e-5, 5e-5) if dtype == F32 else BF16_ATOL
    assert out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32), want_out, atol=atol_out)
    for a, w in zip(vjp(g), want_vjp(g32)):
        assert a.dtype == dtype
        np.testing.assert_allclose(a.astype(jnp.float32), w, atol=atol_grad)
