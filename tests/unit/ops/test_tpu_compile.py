"""The main-path Pallas kernels, compiled for a v5e chip that is described
and not attached (``jax.experimental.topologies``), at gpt2-large and
llama2-7b widths with the serving defaults (8 slots, ``decode_block_kv``
256, ``prefill_chunk`` 64) and the training micro-batch (4 x 1024).

Interpret mode accepts anything; the chip's compiler refuses a block that
overflows VMEM and a relayout Mosaic cannot do. Nothing runs here — a
compile that passes is not a chip run (``chip_smoke.py`` is).

The topology is described inside a module-scoped fixture: only the xdist
worker that is handed this file loads the TPU library, and only once a
test has started.
"""

import collections
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import deepspeed_tpu.ops.pallas as pallas_pkg
from deepspeed_tpu.models import get_model
from deepspeed_tpu.models.transformer import kv_packs, kv_pool_geometry

SLOTS, CHUNK, POOL_LEN = 8, 64, 1024
MODELS = ("gpt2-large", "llama2-7b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(monkeypatch, one_chip):
    """Compile, don't interpret (the backend here is the CPU), and keep the
    persistent cache out of it: an entry written for a described chip cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(pallas_pkg, "interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_(fn, *args):
        args = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), args,
            is_leaf=lambda a: hasattr(a, "shape"))
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text

    yield sds, compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _serving_model(name, **over):
    """The model config ``init_inference(name, dtype=int8, kernel_inject)``
    serves, one layer deep (every layer compiles the same kernels)."""
    cfg = get_model(name).cfg
    cfg = dataclasses.replace(cfg, **{
        "dtype": jnp.bfloat16, "int8_weights": True, "int8_fused_qkv": True,
        "attention_impl": "flash", "scan_layers": False, "num_layers": 1, **over})
    return type(get_model(name))(cfg)


# ------------------------------------------------------------------ training
def _flash_shapes(name):
    """(batch, sequence, config): the training micro-batch of the preset, or
    cell 3's shard of opt-1.3b (``chipbench/configs/opt-1.3b.json``: the
    opt-125m preset at 32 heads of 64, two sequences of 2048 a chip)."""
    if name == "opt-1.3b":
        return 2, 2048, dataclasses.replace(get_model("opt-125m").cfg, hidden_size=2048,
                                            num_heads=32)
    return 4, 1024, get_model(name).cfg


def _flash_layer(for_chip, name):
    """``(forward, its gradient, operands)`` of a layer's flash attention at
    the preset's training shapes."""
    from deepspeed_tpu.ops.pallas.flash_attention import sharded_flash_attention
    sds, _ = for_chip
    batch, seq, cfg = _flash_shapes(name)
    q = sds((batch, cfg.num_heads, seq, cfg.head_size), jnp.bfloat16)
    kv = sds((batch, cfg.kv_heads, seq, cfg.head_size), jnp.bfloat16)

    def fwd(q, k, v):  # as Attention calls it (models/transformer.py), in its module's scope
        with jax.named_scope("attn"):
            return sharded_flash_attention(q, k, v, causal=True,
                                           block_q=cfg.attention_block_q,
                                           block_kv=cfg.attention_block_kv)

    fwd_bwd = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    return fwd, fwd_bwd, (q, kv, kv)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("name", MODELS + ("opt-1.3b", ))
def test_flash_attention(for_chip, name, grad):
    _, compile_ = for_chip
    fwd, fwd_bwd, operands = _flash_layer(for_chip, name)
    text = compile_(fwd_bwd if grad else fwd, *operands)
    # forward, dq and dk/dv stay three calls a layer under the name the
    # device trace had for them: the benchmark's flash_attention_roofline
    # reads ``^(attn|shard_map)[ .].* custom-call$`` and counts a unit of
    # work every three
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == (3 if grad else 1)
    assert all(re.match(r"\s*(ROOT )?%(attn|shard_map)\.\d+ = ", line) for line in calls), calls


def _at_rest(dims, minor_to_major, tile):
    """Elements an array of ``dims`` takes as it rests, over its values: the
    two minor axes of its layout pad to the tile."""
    physical = [dims[i] for i in reversed(minor_to_major)]  # major to minor
    padded = [-(-n // t) * t for n, t in zip(physical[-2:], tile)]
    return padded[0] * padded[1] / (physical[-2] * physical[-1])


@pytest.mark.parametrize("name", ["gpt2-large", "opt-1.3b"], ids=["cell1", "cell3"])
def test_flash_statistics_rest_along_the_lanes(for_chip, name):
    """lse and delta cross HBM between the forward call and the two backward
    calls with the sequence in the lanes: no ``f32[B,H,T,1]`` column under an
    (8, 128) tile (128 times its values: 1.5 GB over cell 1's 36 layers,
    which put its step over the chip's memory), and every float32 array of a
    value a row (lse, delta and each relayout between the reduction that
    makes delta and the kernels) rests within 8x of its values."""
    _, compile_ = for_chip
    _, fwd_bwd, operands = _flash_layer(for_chip, name)
    text = compile_(fwd_bwd, *operands)
    batch, heads, seq, _ = operands[0].shape
    rows = batch * heads * seq
    found = collections.Counter()
    for dims, order, sub, lanes in re.findall(r"f32\[([\d,]+)\]\{([\d,]+):T\((\d+),(\d+)\)", text):
        dims = [int(n) for n in dims.split(",")]
        if math.prod(dims) != rows:
            continue
        assert not (dims[-1] == 1 and (int(sub), int(lanes)) == (8, 128)), (dims, order)
        ratio = _at_rest(dims, [int(i) for i in order.split(",")], (int(sub), int(lanes)))
        assert ratio <= 8, (dims, order, sub, lanes, ratio)
        found[tuple(dims)] += 1
    # what the three calls exchange: a q block's values a row
    assert found[(batch, heads, seq // 256, 1, 256)]


def _rematerialised(text):
    """The entry computation's instructions the compiler's rematerialisation
    pass made (``.remat`` in their names), as the result each computes again.
    The pass runs only on a program over the chip's memory, and buys the
    space back with these."""
    entry = text[text.index("\nENTRY "):]
    return re.findall(r"^\s*(?:ROOT )?%\S*\.remat\S* = \(?(\w+\[[\d,]*\])", entry, re.M)


# (cell, micro-batch a chip or None for the cell's own): rematerialised
# entry instructions. Cell 1 at its own 4 x 1024 sits on the limit
# (``temps_gib`` 6.04 of the 6.20 the state leaves): PR 48's program held 50
# (30 ``up_proj`` products, 12 q/k/v products, 8 in the cross-entropy), with
# the statistics along the lanes one chunk of the cross-entropy's logits and
# the tied embedding's bf16 cast are left.
REMATERIALISED = {
    ("gpt2-large.train.s1024", None): 4,
    ("gpt2-large.train.s1024", 2): 0,
    ("opt-1.3b.train.zero3.s2048", None): 0,
}


@pytest.mark.slow
@pytest.mark.parametrize("cell,micro_batch", sorted(REMATERIALISED, key=str))
def test_train_step_rematerialises(for_chip, topo, monkeypatch, cell, micro_batch):
    """A training cell's whole step at full size through the engine
    (``chipbench.rehearse``'s path), compiled for the described chips: how
    many of its instructions run twice because the step does not fit. ~1 min
    a case."""
    from chipbench import cells, rehearse
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    for name in ("_init_params", "_init_state"):  # rehearse_train replaces them for good
        monkeypatch.setattr(DeepSpeedEngine, name, getattr(DeepSpeedEngine, name))
    texts = []
    monkeypatch.setattr(rehearse, "_report",
                        lambda name, compiled, *a, **kw: texts.append(compiled.as_text()))
    _, workload, root = cells.load_workload(cell)
    if micro_batch:
        workload["train"]["micro_batch_per_chip"] = micro_batch
    rehearse.rehearse_train(workload, cells.load_config(workload["config"], root), topo)
    (text, ) = texts
    found = collections.Counter(_rematerialised(text))
    assert sum(found.values()) == REMATERIALISED[cell, micro_batch], found
    # what is left is the cross-entropy's: no layer's product runs twice
    assert not {"bf16[4,1024,5120]", "bf16[4,20,1024,64]"} & set(found), found
    assert not re.search(r"f32\[\d+,\d+,\d+,1\]\{[^}]*T\(8,128\)", text)


def _gather_riders():
    """``tools/gather_riders.py``, the reader of which product each async
    all-gather of a compiled step rides."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools", "gather_riders.py")
    spec = importlib.util.spec_from_file_location("gather_riders", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _compiled_train_step(monkeypatch, topo, cell, **overrides):
    """The text of a training cell's step through the engine
    (``chipbench.rehearse``'s path), compiled for the described chips, with
    the configuration's ``overrides`` (its depth) replaced."""
    from chipbench import cells, rehearse
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    for name in ("_init_params", "_init_state"):  # rehearse_train replaces them for good
        monkeypatch.setattr(DeepSpeedEngine, name, getattr(DeepSpeedEngine, name))
    texts = []
    monkeypatch.setattr(rehearse, "_report",
                        lambda name, compiled, *a, **kw: texts.append(compiled.as_text()))
    _, workload, root = cells.load_workload(cell)
    config = cells.load_config(workload["config"], root)
    if overrides:
        config = dict(config, overrides=dict(config["overrides"], **overrides),
                      expect={k: v for k, v in config["expect"].items() if k not in overrides})
    rehearse.rehearse_train(workload, config, topo)
    (text, ) = texts
    return text


ZERO3_CELL = "opt-1.3b.train.zero3.s2048"


def test_zero3_step_gathers_the_next_mlp_weight_behind_the_last_product(for_chip, topo,
                                                                        monkeypatch):
    """Two layers of cell 3's step at its widths on the described four
    chips: the program states that layer 1's ``up_proj`` gather (33.5 MB) is
    due behind layer 0's ``down_proj`` product, and the compiler fuses the
    two; left to the partitioner it rode layer 1's attention output product,
    a fifth of its length. ~30 s."""
    riders = _gather_riders()
    text = _compiled_train_step(monkeypatch, topo, ZERO3_CELL, num_layers=2)
    found = riders.gathers(text)
    by_leaf = {(riders.leaf_of(g["op_name"]), g["backward"]): g for g in found
               if g["bytes"] > 2**20}
    ahead = by_leaf["layer_1/mlp/up_proj", False]
    assert not ahead["blocking"] and ahead["shape"] == "bf16[2048,8192]"
    assert [riders.rider_of(name) for name, _ in ahead["riders"]] == ["fwd layer_0/mlp/down_proj"]
    # the second MLP weight stays behind the first's product, and layer 1's
    # first projection rides layer 0's attention, not its down_proj product
    assert [riders.rider_of(name) for name, _ in by_leaf["layer_1/mlp/down_proj", False][
        "riders"]] == ["fwd layer_1/mlp/up_proj"]
    assert [riders.leaf_of(name).split("/")[:2] for name, _ in by_leaf[
        "layer_1/attn/q_proj", False]["riders"]] == [["layer_0", "attn"]]
    # backward: dX is due behind dW, so a regather rides a product of its
    # own weight's size
    for leaf in ("layer_0/mlp/down_proj", "layer_0/mlp/up_proj"):
        behind = by_leaf[leaf, True]
        assert not behind["blocking"]
        assert any(shape.startswith(("bf16[8192,2048", "bf16[2048,8192", "bf16[2,2048,8192"))
                   for _, shape in behind["riders"]), behind
    # down_proj's regather behind its OWN dW (the weight's shape)
    assert any(shape.startswith("bf16[8192,2048") and "down_proj" in name
               for name, shape in by_leaf["layer_0/mlp/down_proj", True]["riders"])
    # the same text by what each entry instruction is to the gathers: a
    # traced run's time by operation name sums under those kinds
    kinds = riders.kinds(text)
    step = next(name for name, (kind, detail) in kinds.items() if kind == "gather step"
                and detail.startswith("fwd mlp/up_proj <- fwd mlp/down_proj"))
    assert sum(kind == "reduce-scatter" for kind, _ in kinds.values()) >= 12
    times = riders.timed(text, {f"{step} bf16[2,2048,2048]": (0.002, 2), "%nowhere.1": (0.001, 1)},
                         steps=2)
    assert times["gather step", kinds[step][1]] == [1.0, 1.0]
    assert times["not in the text", "%nowhere"] == [0.5, 0.5]


@pytest.mark.slow
def test_zero3_step_rides_every_large_gather_on_a_product_of_its_size(for_chip, topo,
                                                                      monkeypatch):
    """Cell 3's whole step (24 layers): which product each large gather
    rides, forward and backward, by the ``op_name`` of the product inside the
    fusion; no MLP gather left behind an attention projection's product
    except layer 0's; no weight's gather blocking but layer 0's first two
    (nothing stands in front of them but the lookup); the regathers all
    there (ZeRO-3's memory, not ZeRO-2's); nothing rematerialised. ~2.5 min."""
    riders = _gather_riders()
    text = _compiled_train_step(monkeypatch, topo, ZERO3_CELL)
    found = [g for g in riders.gathers(text) if g["bytes"] > 2**20]
    layers = 24
    weights = [g for g in found if re.match(r"layer_\d+/", riders.leaf_of(g["op_name"]))]
    blocking = sorted(riders.leaf_of(g["op_name"]) for g in weights if g["blocking"])
    assert all(leaf.startswith("layer_0/attn/") for leaf in blocking) and len(blocking) <= 2
    # every weight gathered twice a step, but where a forward's gather is
    # still there for the backward next to it (the last layer's)
    assert 2 * 6 * layers - 4 <= len(weights) <= 2 * 6 * layers
    table = collections.Counter()
    for g in weights:
        if g["blocking"]:
            continue
        leaf = riders.leaf_of(g["op_name"])
        biggest = max(g["riders"], key=lambda r: riders._shape_bytes(r[1]))
        big_rider = riders._shape_bytes(biggest[1]) >= 2 * 2048 * 8192 * 2 or "/mlp/" in biggest[0]
        table[leaf.split("/", 1)[1], "backward" if g["backward"] else "forward", big_rider] += 1
        if "/mlp/" in leaf and not leaf.startswith("layer_0/"):
            assert big_rider, (leaf, g["backward"], g["riders"])
    # forward: 23 up_proj gathers behind the layer below's down_proj product
    assert table["mlp/up_proj", "forward", True] >= layers - 1
    assert table["mlp/down_proj", "forward", True] == layers
    assert table["mlp/down_proj", "backward", True] >= layers - 1
    assert table["mlp/up_proj", "backward", True] == layers
    assert not _rematerialised(text)


# ------------------------------------------------------------ decode kernels
def _pool(sds, cfg, int8):
    """One layer's cache operands in the geometry ``init_cache`` gives the
    model: ``(k, v)`` as the kernels' entry points take them (the packed
    leaf of gpt2-large's 64-wide heads as ``k`` with ``v`` None; llama2-7b's
    split pair), the leaves alone, and the int8 tier's scale leaf."""
    packed = kv_packs(cfg.head_size)
    assert packed == (cfg.head_size == 64)
    shape = (SLOTS, cfg.kv_heads, POOL_LEN, (2 if packed else 1) * cfg.head_size)
    leaf = sds(shape, jnp.int8 if int8 else jnp.bfloat16)
    scale = sds((SLOTS, 1, POOL_LEN, 1), jnp.float16) if int8 else None
    return ((leaf, None), (leaf, ), scale) if packed else ((leaf, leaf), (leaf, leaf), scale)


@pytest.mark.parametrize("variant", ["dense", "paged", "paged_int8kv", "span",
                                     "span_int8kv", "span_extents"])
@pytest.mark.parametrize("name", MODELS)
def test_decode_attention(for_chip, name, variant):
    from deepspeed_tpu.ops.pallas import decode_attention as da
    sds, compile_ = for_chip
    cfg = get_model(name).cfg
    block = cfg.decode_block_kv
    (k, v), _, scale = _pool(sds, cfg, "int8kv" in variant)
    rows = sds((SLOTS, ), jnp.int32)
    q1 = sds((SLOTS, cfg.num_heads, cfg.head_size), jnp.bfloat16)
    qT = sds((SLOTS, cfg.num_heads, CHUNK, cfg.head_size), jnp.bfloat16)
    if variant == "dense":
        compile_(lambda q, k, v, st, end: da.decode_attention(
            q, k, v, st, end, block_kv=block), q1, k, v, rows, sds((), jnp.int32))
    elif variant == "paged":
        compile_(lambda q, k, v, st, en: da.paged_decode_attention(
            q, k, v, st, en, block_kv=block), q1, k, v, rows, rows)
    elif variant == "paged_int8kv":
        compile_(lambda q, k, v, st, en, sc: da.paged_decode_attention(
            q, k, v, st, en, block_kv=block, k_scale=sc, v_scale=sc),
            q1, k, v, rows, rows, scale)
    elif variant == "span":
        compile_(lambda q, k, v, st, ba: da.paged_span_attention(
            q, k, v, st, ba, block_kv=block), qT, k, v, rows, rows)
    elif variant == "span_int8kv":
        compile_(lambda q, k, v, st, ba, sc: da.paged_span_attention(
            q, k, v, st, ba, block_kv=block, k_scale=sc, v_scale=sc),
            qT, k, v, rows, rows, scale)
    else:
        compile_(lambda q, k, v, st, ba, ext: da.paged_span_attention(
            q, k, v, st, ba, block_kv=block, ext=ext, sink=st, window=st),
            qT, k, v, rows, rows, sds((SLOTS, 2), jnp.int32))


# (slots, query heads, kv heads, head size, pool rows, span, packed) of the
# attention calls the benchmark's serving cells make: cell 2's one column
# over 24 packed slots and its chunk as a (1, 64) span over one slot, cell
# 5's split leaves, cell 6's grouped queries over 4096 shared rows and over
# its 512-key rings
CELL_ATTENTION = {
    "gpt2-large.column": (24, 20, 20, 64, 1024, 0, True),
    "gpt2-large.chunk": (1, 20, 20, 64, 1024, 64, True),
    "olmo-hybrid.column": (64, 30, 30, 128, 1024, 0, False),
    "phi-4-flash.column": (64, 40, 10, 128, 4096, 0, False),
    "phi-4-flash.ring": (64, 40, 10, 128, 512, 0, False),
}


@pytest.mark.parametrize("call", sorted(CELL_ATTENTION))
def test_decode_attention_cell_shapes(for_chip, call):
    """The in-kernel walk (two VMEM buffers, DMA semaphores, a loop of a
    dynamic trip count) at the shapes the cells run: a VMEM overflow or a
    copy Mosaic refuses fails here, not on the chip."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    sds, compile_ = for_chip
    B, H, nkv, D, S, span, packed = CELL_ATTENTION[call]
    leaf = sds((B, nkv, S, (2 if packed else 1) * D), jnp.bfloat16)
    k, v = (leaf, None) if packed else (leaf, leaf)
    rows = sds((B, ), jnp.int32)
    if span:
        text = compile_(lambda q, k, v, st, ba: da.paged_span_attention(q, k, v, st, ba),
                        sds((B, H, span, D), jnp.bfloat16), k, v, rows, rows)
    else:
        text = compile_(lambda q, k, v, st, en: da.paged_decode_attention(q, k, v, st, en),
                        sds((B, H, D), jnp.bfloat16), k, v, rows, rows)
    assert "dstpu_decode_attn" in text


@pytest.mark.parametrize("cols", [1, CHUNK], ids=["decode", "span"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16kv", "int8kv"])
@pytest.mark.parametrize("name", MODELS)
def test_kv_commit(for_chip, name, int8, cols):
    from deepspeed_tpu.ops.pallas.kv_commit import commit_kv_rows
    sds, compile_ = for_chip
    cfg = get_model(name).cfg
    _, leaves, _ = _pool(sds, cfg, int8)
    fresh = tuple(sds(c.shape[:2] + (cols, c.shape[3]), c.dtype) for c in leaves)
    rows = sds((SLOTS, ), jnp.int32)
    text = compile_(commit_kv_rows, leaves, fresh, rows, rows)
    assert "dstpu_kv_commit" in text


@pytest.mark.parametrize("slots, cols, width, pool_len", [
    (64, 1, 320, 2048), (1, 256, 320, 2048), (192, 1, 576, 4096), (1, 512, 576, 4096)],
    ids=["cell4-decode", "cell4-chunk", "cell10-decode", "cell10-chunk"])
def test_kv_commit_columns(for_chip, slots, cols, width, pool_len):
    """The latent leaf's column commit at the two cells' shapes: a decode
    step's one column a slot, and the chunk's span into its own slot."""
    from deepspeed_tpu.ops.pallas.kv_commit import commit_kv_columns
    sds, compile_ = for_chip
    rows = sds((slots, ), jnp.int32)
    text = compile_(commit_kv_columns, sds((slots, 1, width, pool_len), jnp.bfloat16),
                    sds((slots, 1, cols, width), jnp.bfloat16), rows, rows)
    assert "dstpu_kv_commit_columns" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_gdn_step(for_chip, dtype):
    """The one-token gated-delta update alone at cell 5's shape: 64 slots of
    30 heads (96, 192), two heads a lane row at rest."""
    from deepspeed_tpu.ops.pallas import gdn_step
    sds, compile_ = for_chip
    slots, n, dk, dv = 64, 30, 96, 192
    leaf = sds((slots, n // 2, dk, 2 * dv), dtype)
    assert gdn_step.tiles(leaf, n, dk, dv)
    f32 = jnp.float32
    text = compile_(gdn_step.gated_delta_update, leaf, sds((slots, n, dk), f32),
                    sds((slots, n, dk), f32), sds((slots, n, dv), f32), sds((slots, n), f32),
                    sds((slots, n), f32), sds((slots, ), jnp.bool_), sds((slots, ), jnp.bool_))
    assert "dstpu_gdn_step" in text


@pytest.mark.parametrize("slots, nh, hd, N, G, dtype", [
    (192, 64, 64, 128, 8, jnp.bfloat16), (64, 32, 128, 256, 2, jnp.bfloat16),
    (8, 16, 8, 128, 1, jnp.float32)], ids=["cell7", "cell11", "small-f32"])
def test_ssd_step(for_chip, slots, nh, hd, N, G, dtype):
    """The one-token Mamba-2 update alone at the two cells' shapes: 192 slots
    of 64 heads (64, 128) in 8 groups, 64 slots of 32 heads (128, 256) in 2;
    and a float32 leaf of half a megabyte, sixteen heads a piece (with its
    result declared ``pltpu.HBM`` the compiler aborts on this one:
    ``ssd_step.py``'s docstring)."""
    from deepspeed_tpu.ops.pallas import ssd_step
    sds, compile_ = for_chip
    leaf = sds((slots, nh, hd, N), dtype)
    assert ssd_step.tiles(leaf, nh, hd, N, G)
    f32 = jnp.float32
    text = compile_(ssd_step.ssd_update, leaf, sds((slots, nh, hd), f32), sds((slots, nh), f32),
                    sds((nh, ), f32), sds((slots, G, N), f32), sds((slots, G, N), f32),
                    sds((nh, ), f32), sds((slots, ), jnp.bool_), sds((slots, ), jnp.bool_))
    assert "dstpu_ssd_step" in text


# ------------------------------------------------------- fused decode blocks
def _layer_operands(name):
    """Shapes of the operand tuples the engines hand the fused kernels:
    ``fused_decode_operands`` over the int8 module's own param tree."""
    model = _serving_model(name)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    (layer, ), head = jax.eval_shape(model.fused_decode_operands, params)
    return model.cfg, layer, head


@pytest.mark.parametrize("rows", [SLOTS, SLOTS * CHUNK], ids=["decode", "span"])
@pytest.mark.parametrize("kernel", ["fused_qkv_ln", "fused_out_mlp", "logits"])
@pytest.mark.parametrize("name", MODELS)
def test_fused_block(for_chip, name, kernel, rows):
    from deepspeed_tpu.models.transformer import _qmm2d
    from deepspeed_tpu.ops.pallas import decode_block as db
    sds, compile_ = for_chip
    cfg, (norms, qkv, o, up, down, gate), head = _layer_operands(name)
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_size
    x = sds((rows, cfg.hidden_size), jnp.bfloat16)
    if kernel == "fused_qkv_ln":
        rope = sds((rows, hd // 2), jnp.float32) if cfg.pos_embedding == "rope" else None
        compile_(lambda x, n, w, r: db.fused_qkv_ln(
            x, n, w, eps=cfg.layernorm_epsilon, norm=cfg.norm,
            rope=None if r is None else (r, r, nh + nkv, hd)), x, norms, qkv, rope)
    elif kernel == "fused_out_mlp":
        compile_(lambda a, x, n, o, up, down, gate: db.fused_out_mlp(
            a, x, n, o, up, down, activation=cfg.activation,
            eps=cfg.layernorm_epsilon, norm=cfg.norm, gate=gate),
            sds((rows, nh * hd), jnp.bfloat16), x, norms, o, up, down, gate)
    else:
        compile_(_qmm2d, x, head["logits_q"], head["logits_scale"])


@pytest.mark.parametrize("step", ["decode", "span", "span_int8kv"])
@pytest.mark.parametrize("name", MODELS)
def test_scheduler_step_program(for_chip, name, step):
    """The continuous-batching step as the scheduler builds it —
    ``fused_paged_step`` over the ``(num_slots, prefill_chunk)`` span (or
    the one-column decode), bf16 and int8 pools — one layer deep."""
    sds, compile_ = for_chip
    model = _serving_model(name)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    pool = jax.eval_shape(lambda: model.init_cache(
        SLOTS, POOL_LEN, quantized=step.endswith("int8kv")))
    assert kv_pool_geometry(model.cfg, pool) == ("packed" if name == "gpt2-large" else "split")
    cols = 1 if step == "decode" else CHUNK
    ids = sds((SLOTS, cols), jnp.int32)
    rows = sds((SLOTS, ), jnp.int32)
    compile_(model.fused_paged_step, params, ids, pool, ids, rows, rows)


def _pool_relayouts(text, shape):
    """Instructions of a compiled program that move a whole pool leaf
    (``copy``, ``scatter`` or ``transpose`` with a ``shape`` result, fused
    or not): (inside the ``while`` bodies and what they call, elsewhere)."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    called = {n: set(re.findall(r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)",
                                "\n".join(lines))) for n, lines in comps.items()}
    inside = set(re.findall(r"body=(%[\w.\-]+)", text))
    todo = list(inside)
    while todo:
        new = called[todo.pop()] - inside
        inside |= new
        todo += new
    moves = re.compile(r"= \w+" + re.escape(shape) + r"\S* (copy|scatter|transpose)\(")
    count = lambda names: sum(bool(moves.search(l)) for n in names for l in comps[n])
    return count(inside), count(set(comps) - inside)


def _leaf_moves(text, shape):
    """:func:`_pool_relayouts` of a pool leaf in both of its spellings, as the
    tree holds it and with its unit dimensions dropped (the latent leaf ``(N,
    1, D, S)`` is ``(N, D, S)`` to the block walk: a transposition for the
    walk carries no unit dimension, and a meter that asks for one spelling
    reads 0 beside it). The asynchronous copies count too, around the loops
    (``copy-start`` of the whole leaf: the compiler parks a leaf that fits
    VMEM there, cell 4's 84 MB, and moves it out and in again every step
    unless the commit's result is declared HBM's; 3.5% of cell 4's window
    on the chip, PR 55)."""
    spell = lambda dims: "[" + ",".join(map(str, dims)) + "]"
    spellings = {spell(shape), spell([d for d in shape if d != 1])}
    in_loop, around = map(sum, zip(*(_pool_relayouts(text, s) for s in spellings)))
    parked = sum(" copy-start(" in line and any(s in line for s in spellings)
                 for line in text.splitlines())
    return in_loop, around + parked


def _fused_sync(model, steps):
    """A sync as ``DecodeScheduler._fused_fn`` builds it on the fused int8
    path: a first forward over the ids block, then a ``fori_loop`` of
    one-column steps on the donated pool (``steps`` in all)."""
    def sync(params, pool, ids, lengths, spans):
        pos = lengths[:, None] + jnp.arange(ids.shape[1])[None, :]
        logits, pool = model.fused_paged_step(params, ids, pool, pos, lengths, spans)
        base = lengths + jnp.maximum(spans, 1) - 1
        live = jnp.minimum(spans, 1)

        def body(k, carry):
            pool, tok = carry
            lg, pool = model.fused_paged_step(
                params, tok[:, None], pool, (base + k)[:, None], base + k, live)
            return pool, jnp.argmax(lg[:, 0], -1).astype(jnp.int32)

        return jax.lax.fori_loop(
            1, steps, body, (pool, jnp.argmax(logits[:, 0], -1).astype(jnp.int32)))

    return sync


def _compile_fused_sync(sds, model, slots, pool_len, steps):
    shaped = lambda tree: jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)
    params = shaped(jax.eval_shape(model.init_params, jax.random.key(0)))
    pool = shaped(jax.eval_shape(lambda: model.init_cache(slots, pool_len)))
    rows = sds((slots, ), jnp.int32)
    compiled = jax.jit(_fused_sync(model, steps), donate_argnums=(1, )).lower(
        params, pool, sds((slots, CHUNK), jnp.int32), rows, rows).compile()
    text = compiled.as_text()
    assert "dstpu_kv_commit" in text and " while(" in text
    leaves = jax.tree_util.tree_leaves(pool)
    # the donated pool is updated in place: every leaf aliased input to output
    assert len(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", text)) == len(leaves)
    return compiled, _pool_relayouts(text, "[" + ",".join(map(str, leaves[0].shape)) + "]")


@pytest.mark.parametrize("name", MODELS)
def test_step_loop_carries_the_pool_in_one_layout(for_chip, name):
    """A sync as ``DecodeScheduler._fused_fn`` builds it, one layer deep: a
    first forward, then a ``fori_loop`` of one-column steps on the donated
    pool. The commit kernel and the attention kernels both take the pool
    row-major, and that is the form every leaf rests in (llama2-7b's
    128-wide split leaves; gpt2-large's 64-wide heads packed K beside V in
    one 128-lane leaf), so no operation moves a whole leaf: none in the loop
    body and none around it. The scatter the commit kernel replaced put
    four in the body and eight around it; the split 64-wide leaves, which
    rest position-major, one in and one out for each leaf."""
    sds, _ = for_chip
    _, (in_loop, around) = _compile_fused_sync(sds, _serving_model(name), SLOTS, POOL_LEN, 3)
    assert (in_loop, around) == (0, 0)


def test_fused_sync_temporaries_at_cell_2(for_chip):
    """The gpt2-large sync at the chip benchmark's serving shape (24 slots x
    1024, ``prefill_chunk`` 64, ``steps_per_sync`` 4), one layer deep: with
    the split 64-wide leaves the program held 820 MB of temporaries, the
    row-major copies of its two pool leaves at 126 MB each among them
    (ISSUE 29's probe of the parent); the packed leaf needs no copy, and the
    program stays under half of that."""
    sds, _ = for_chip
    compiled, (in_loop, around) = _compile_fused_sync(
        sds, _serving_model("gpt2-large"), 24, 1024, 4)
    assert (in_loop, around) == (0, 0)
    mem = compiled.memory_analysis()
    print("temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 410e6, mem


def _sync(model, chunk, fused=False):
    """A sync of ``DecodeScheduler._fused_fn``'s program at ``steps_per_sync``
    4: the first forward over one column (``chunk`` 1, the decode program) or
    over the live rows of a (slots, chunk) block (the scheduler's own split),
    then a ``fori_loop`` of one-column steps on the donated pool, all through
    ``apply_with_cache`` (the plain per-projection program) or, ``fused``,
    through ``fused_paged_step`` (the fused int8 decode blocks)."""
    from deepspeed_tpu.inference.scheduler import _first_forward_live_rows, _split_pays

    def sync(params, pool, ids, lengths, spans):
        def forward(pool, ids, pos, widx, sp):
            if fused:
                return model.fused_paged_step(params, ids, pool, pos, widx, sp) + (None, None)
            return model.apply_with_cache(params, ids, pool, 0, position_ids=pos,
                                          write_index=widx, q_spans=sp) + (None, None)

        if chunk == 1:
            logits, pool, _, _ = forward(pool, ids, lengths[:, None], lengths, spans)
            last = logits[:, 0]
        else:
            assert _split_pays(ids.shape[0], chunk, 1 if fused else 2)
            last, pool, _, _ = _first_forward_live_rows(forward, pool, ids, lengths, spans)
        base_ = lengths + jnp.maximum(spans, 1) - 1
        live = jnp.minimum(spans, 1)

        def body(k, carry):
            pool, tok = carry
            lg, pool, _, _ = forward(pool, tok[:, None], (base_ + k)[:, None], base_ + k, live)
            return pool, jnp.argmax(lg[:, 0], -1).astype(jnp.int32)

        return jax.lax.fori_loop(1, 4, body, (pool, jnp.argmax(last, -1).astype(jnp.int32)))

    return sync


def _lower_sync(sds, model, slots, chunk, pool_len, fused=False):
    """(the lowered sync, its pool's shapes); an int8 model's parameters keep
    their own dtypes, a float model's are bf16, every leaf at rest in the
    device's default layout, as the engine leaves them."""
    shaped = lambda tree, dt=None: jax.tree_util.tree_map(
        lambda a: sds(a.shape, dt or a.dtype), tree)
    params = shaped(jax.eval_shape(model.init_params, jax.random.key(0)),
                    None if fused else jnp.bfloat16)
    pool = shaped(jax.eval_shape(lambda: model.init_cache(slots, pool_len)))
    rows = sds((slots, ), jnp.int32)
    return jax.jit(_sync(model, chunk, fused), donate_argnums=(1, )).lower(
        params, pool, sds((slots, chunk), jnp.int32), rows, rows), pool


def _compile_sync(sds, model, slots, chunk, pool_len, fused=False):
    lowered, pool = _lower_sync(sds, model, slots, chunk, pool_len, fused)
    compiled = lowered.compile()
    # the donated pool is updated in place: every leaf is aliased input to
    # output and nothing in the loop moves a whole leaf; ``around`` counts
    # the whole-leaf moves outside it (a relayout in and out of a leaf that
    # rests in another form than the program reads)
    text = compiled.as_text()
    leaves = jax.tree_util.tree_leaves(pool)
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", text)
    assert len(aliased) == len(leaves), (len(aliased), len(leaves))
    in_loop, around = _pool_relayouts(text, "[" + ",".join(map(str, leaves[0].shape)) + "]")
    assert in_loop == 0, in_loop
    return compiled, pool, around


@pytest.mark.parametrize("step", ["decode", "span"])
def test_latent_moe_step_program(for_chip, step):
    """Mistral-Small-4-119B's sync at the published widths as the chip
    benchmark serves it (64 slots, ``prefill_chunk`` 256, a 2,048-position
    latent pool, 32 of the 128 experts held, a quarter of the vocabulary),
    one layer deep: latent attention over the pool, the span commit of latent
    columns, the routed experts. The latent leaf rests position-last, the
    form the block walk's two products take, and its commit is the in-place
    column kernel: NOTHING moves the whole leaf, in the steps' loop or around
    it, in either spelling (until PR 55 the span scatter wanted a position's
    320 values minor and the walk the positions: one relayout on entry, one a
    forward for the walk, the one inside the loop spelt without the unit
    dimension where this test did not look, and one on exit). The chunk sync runs its live rows only (the 64
    decode rows as one column by the sparse dispatch, 2 rows an expert; the
    chunk as a (1, 256) forward over its own slot by the dense product over
    the 32 experts held, 8 rows an expert), and needs less room than the
    whole block's 1.99 GB."""
    sds, _ = for_chip
    slots, chunk, pool_len = 64, 256, 2048
    base = get_model("mistral-small-4-119b")
    model = type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=1, moe_experts_held=32, vocab_size=32768,
        max_seq_len=8192, attention_impl="flash", scan_layers=False))
    compiled, pool, around = _compile_sync(
        sds, model, slots, 1 if step == "decode" else chunk, pool_len)
    assert jax.tree_util.tree_leaves(pool)[0].shape == (slots, 1, 320, pool_len)
    text = compiled.as_text()
    assert "dstpu_kv_commit_columns" in text
    assert _leaf_moves(text, (slots, 1, 320, pool_len)) == (0, 0)
    assert "ragged-dot" in text and "tpu_custom_call" in text  # the grouped products, on the chip
    # the chunk's forward multiplies its 256 rows by all 32 held experts, and
    # reads the kernels as they rest: no whole leaf is copied for it
    assert ("bf16[32,256,2048]" in text) is (step == "span")
    for kernel in ("[32,4096,2048]", "[32,2048,4096]"):
        assert _pool_relayouts(text, kernel) == (0, 0), kernel
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.99e9, mem
    print(step, "temporaries", mem.temp_size_in_bytes)


def test_dense_per_projection_chunk_sync(for_chip):
    """gpt2-large in bf16 on the per-projection path at cell 2's shape (24
    slots x 1024, ``prefill_chunk`` 64), one layer deep: the chunk sync that
    runs 24 + 64 live rows in place of the 1,536 of its block."""
    sds, _ = for_chip
    base = get_model("gpt2-large")
    model = type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=1, attention_impl="flash", scan_layers=False))
    compiled, pool, around = _compile_sync(sds, model, 24, 64, 1024)
    assert jax.tree_util.tree_leaves(pool)[0].shape == (24, 20, 1024, 128)  # packed
    assert around == 0
    print("temporaries", compiled.memory_analysis().temp_size_in_bytes)


def test_fused_int8_chunk_sync_at_cell_2(for_chip):
    """Cell 2's own chunk sync (gpt2-large, int8 fused decode blocks, packed
    pool, 24 slots x 1024, ``prefill_chunk`` 64, K = 4) as the scheduler
    splits it: the 24 decode rows as one column and the chunk as a (1, 64)
    forward over its own slot. One layer deep, compiled: the pool aliased, no
    copy, scatter or transpose of a whole pool leaf (one slot's rows go out
    and back by a dynamic slice and an in-place update), nothing of the
    block's 1,536 rows, and temporaries far under the block program's. At the
    model's 36 layers, lowered: each of the three layer kernels and the
    commit is ONE function a row count (24 and 64), called from every layer
    (and, at 24 rows, from the column and the loop body alike), not 36
    bodies."""
    sds, _ = for_chip
    compiled, pool, around = _compile_sync(sds, _serving_model("gpt2-large"), 24, 64, 1024,
                                           fused=True)
    assert jax.tree_util.tree_leaves(pool)[0].shape == (24, 20, 1024, 128)  # packed
    assert around == 0
    text = compiled.as_text()
    assert "dstpu_kv_commit" in text and "[1536," not in text
    for kernel in ("dstpu_fused_qkv_ln", "dstpu_fused_out_mlp", "dstpu_decode_attn"):
        assert kernel in text, kernel  # none fell back to an XLA path
    block, _ = _compile_fused_sync(sds, _serving_model("gpt2-large"), 24, 1024, 4)
    temps, block_temps = (c.memory_analysis().temp_size_in_bytes for c in (compiled, block))
    print("temporaries", temps, "block", block_temps)
    assert temps < block_temps / 4

    lowered, _ = _lower_sync(sds, _serving_model("gpt2-large", num_layers=36), 24, 64, 1024,
                             fused=True)
    module = lowered.as_text()
    calls = collections.Counter(re.findall(r"call @(\w+?)(?:_\d+)?\(", module))
    bodies = collections.Counter(re.findall(r"func\.func private @(\w+?)(?:_\d+)?\(", module))
    for fn, kernel in (("_qkv_ln", "dstpu_fused_qkv_ln"), ("_out_mlp", "dstpu_fused_out_mlp"),
                       ("_decode_jit", "dstpu_decode_attn"), ("_commit", "dstpu_kv_commit")):
        # two row counts; the column and the loop body share the 24-row one
        assert bodies[fn] == 2 and calls[fn] == 3 * 36, (fn, bodies[fn], calls[fn])
        assert module.count(kernel) == 2, (kernel, module.count(kernel))


def test_generate_step_program(for_chip):
    """``InferenceEngine._fused_step``'s layer: ``fused_decode_block`` with
    the static-batch cache, gpt2-large, B=8."""
    from deepspeed_tpu.ops.pallas.decode_block import fused_decode_block
    sds, compile_ = for_chip
    cfg, (norms, qkv, o, up, down, gate), _ = _layer_operands("gpt2-large")
    _, leaves, _ = _pool(sds, cfg, False)
    compile_(lambda x, n, kv, qkv, o, up, down, st, pos: fused_decode_block(
        x, n, kv, qkv, o, up, down, st, pos, activation=cfg.activation,
        eps=cfg.layernorm_epsilon, block_kv=cfg.decode_block_kv, norm=cfg.norm),
        sds((SLOTS, cfg.hidden_size), jnp.bfloat16), norms, leaves, qkv, o, up,
        down, sds((SLOTS, ), jnp.int32), sds((), jnp.int32))


@pytest.mark.parametrize("step", ["decode", "span"])
def test_hybrid_state_step_program(for_chip, step):
    """Olmo-Hybrid-7B's sync at the published widths as the chip benchmark
    serves it (64 slots x 1024, ``prefill_chunk`` 128, ``steps_per_sync`` 4,
    the whole vocabulary), one period deep (three gated-delta-rule layers and
    a full-attention one): the one-token update of 64 states and, in the
    chunk sync, the 128-token scan over the chunk's own slot. The donated
    pool is updated in place: no whole-leaf copy, scatter or transpose of a
    state leaf, a window leaf or a K/V leaf stands in the loop or around it
    (a per-row gather of the window's three rows once left them in the
    lanes, padded forty-fold: 180 MB a copy for a 4 MB leaf). It fits with
    its temporaries: all sixteen layers' weights and pool are 13.1 GB of the
    chip's 15.75 GiB (the state leaves rest unpadded since PR 42: 0.28 GB
    less), and a period's sync holds under 0.4 GB beside them (0.25 GB the
    decode sync, 0.31 GB the chunk's; all sixteen layers': 1.15 GB, compiled
    once by hand, PR 30). The one-token update is ``dstpu_gdn_step`` on the
    packed leaf ``(64, 15, 96, 384)``."""
    sds, _ = for_chip
    slots, chunk, pool_len = 64, 128, 1024
    base = get_model("olmo-hybrid-7b")
    model = type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=4, layer_types=base.cfg.layer_types[:4],
        max_seq_len=pool_len, attention_impl="flash"))
    compiled, pool, around = _compile_sync(
        sds, model, slots, 1 if step == "decode" else chunk, pool_len)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    # two heads side by side in a state's lanes (PR 42): 384, not 192 at 256
    assert shapes[0] == (slots, 15, 96, 384) and around == 0
    text = compiled.as_text()
    for shape in set(shapes):
        assert _pool_relayouts(text, "[" + ",".join(map(str, shape)) + "]") == (0, 0), shape
    assert "dstpu_decode_attn" in text and "dstpu_kv_commit" in text
    assert "dstpu_gdn_step" in text  # the one-token update, in place
    mem = compiled.memory_analysis()
    print(step, "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 0.4e9, mem
    whole = 4 * (mem.argument_size_in_bytes - 2 * 3840 * 100352 * 2) + 2 * 3840 * 100352 * 2
    assert whole + mem.temp_size_in_bytes < 15.75 * 2**30, whole


@pytest.mark.parametrize("step", ["decode", "span"])
def test_sambay_step_program(for_chip, step):
    """Phi-4-mini-flash-reasoning's sync at the published widths as the chip
    benchmark serves it (64 slots x 4096, ``prefill_chunk`` 512,
    ``steps_per_sync`` 4, the whole vocabulary), with one layer of each kind
    (Mamba, windowed differential attention, Mamba, the full differential
    layer, a gated memory unit, a cross-attention layer): the one-token update
    of 64 SSM states and, in the chunk sync, the 512-token scan, the ring
    attended before its commit and the cross layer reading the full layer's
    rows, all over the chunk's own slot. The donated pool is updated in
    place: no whole-leaf copy, scatter or transpose of a state, window, ring
    or rows leaf stands in the loop or around it; a ring leaf is 512 rows
    long whatever the pool's length. It fits with its temporaries: the 32
    layers' weights (7.71 GB) and pool (3.48 GB) are 11.2 GB of the chip's
    15.75 GiB (all 32 layers compiled once by hand, PR 32: temporaries 0.15
    GB decode, 0.26 GB chunk; 10.0 GiB in all)."""
    sds, _ = for_chip
    slots, chunk, pool_len = 64, 512, 4096
    base = get_model("phi-4-mini-flash-reasoning")
    kinds = ("mamba", "diff_attention", "mamba", "diff_attention", "gmu", "cross_attention")
    model = type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=6, layer_types=kinds,
        layer_windows=(0, 512, 0, 0, 0, 0), max_seq_len=pool_len, attention_impl="flash"))
    compiled, pool, around = _compile_sync(
        sds, model, slots, 1 if step == "decode" else chunk, pool_len)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    assert sorted(set(shapes)) == [(slots, 1, 3, 5120), (slots, 1, 16, 5120),
                                   (slots, 10, 512, 128), (slots, 10, pool_len, 128)]
    assert around == 0
    text = compiled.as_text()
    for shape in set(shapes):
        assert _pool_relayouts(text, "[" + ",".join(map(str, shape)) + "]") == (0, 0), shape
    assert "dstpu_decode_attn" in text and "dstpu_kv_commit" in text
    mem = compiled.memory_analysis()
    print(step, "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 2.0e9, mem
    assert 7.71e9 + 3.48e9 + mem.temp_size_in_bytes < 15.75 * 2**30


def _nemotron_share(kinds, pool_len=4096):
    """NVIDIA-Nemotron-3-Nano-30B-A3B as the chip benchmark cuts it (experts
    0-63 of 128, half the vocabulary), with the layers ``kinds``."""
    base = get_model("nemotron-3-nano-30b-a3b")
    return type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=len(kinds), layer_types=kinds,
        moe_experts_held=64, vocab_size=65536, max_seq_len=pool_len, attention_impl="flash"))


@pytest.mark.parametrize("step", ["decode", "span"])
def test_nemotron_h_step_program(for_chip, step):
    """NVIDIA-Nemotron-3-Nano-30B-A3B's sync at the published widths as the
    chip benchmark serves it (192 slots x 4096, ``prefill_chunk`` 512,
    ``steps_per_sync`` 4, experts 0-63 of 128, half the vocabulary), with one
    layer of each kind (a Mamba-2 mixer, an expert layer, attention, each a
    block of ONE sublayer): the one-token update of 192 states of 64 x 64 x
    128 in place (``dstpu_ssd_step``) and, in the chunk sync, the chunked matrix form over the chunk's own
    slot, attention without positions through the paged kernels, and the
    192 x 6 pairs (9 rows an expert; the chunk's 512 rows: 24) by the dense
    product over the 64 experts held: no grouped product is left in either
    program, and the expert kernels are read as they rest (the device's
    default layout: 2,688 in the lanes of both), no copy or transpose of a
    whole ``[64, 2688, 1856]`` leaf (0.64 GB each).
    The donated pool is updated in place: no whole-leaf copy, scatter or
    transpose of a state, window or K/V leaf stands in the loop or around
    it. It fits with its temporaries: the 16 layers' weights (10.57 GB) and
    pool (3.07 GB) are 13.6 GB of the chip's 15.75 GiB, and one expert
    layer's products hold under a whole kernel's size beside them."""
    sds, _ = for_chip
    slots, chunk, pool_len = 192, 512, 4096
    model = _nemotron_share(("mamba2", "moe", "attention"), pool_len)
    compiled, pool, around = _compile_sync(
        sds, model, slots, 1 if step == "decode" else chunk, pool_len)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    assert sorted(set(shapes)) == [(slots, 1, 3, 6144), (slots, 2, pool_len, 128),
                                   (slots, 64, 64, 128)]
    assert around == 0
    text = compiled.as_text()
    for shape in set(shapes):
        assert _pool_relayouts(text, "[" + ",".join(map(str, shape)) + "]") == (0, 0), shape
    assert "dstpu_decode_attn" in text and "dstpu_kv_commit" in text
    # the one-token update on the state leaf in place (a chunk's sync ends in
    # decode substeps), the leaf neither moved nor parked
    assert "dstpu_ssd_step" in text
    assert _leaf_moves(text, (slots, 64, 64, 128)) == (0, 0)
    assert "ragged-dot" not in text and "moe_experts" in text
    for kernel in ("[64,2688,1856]", "[64,1856,2688]"):
        assert _pool_relayouts(text, kernel) == (0, 0), kernel
    mem = compiled.memory_analysis()
    print(step, "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 0.6e9, mem
    assert 10.57e9 + 3.07e9 + mem.temp_size_in_bytes < 15.75 * 2**30, mem


@pytest.mark.slow
def test_nemotron_h_whole_share_reads_each_window_leaf_once(for_chip):
    """Cell 7's collecting chunk sync at its whole depth (16 layers, 2.5
    minutes to compile: the slow lane). Sixteen layers deep the compiler
    rematerialises cheap fusions, and with the state leaves off XLA's hands
    (``dstpu_ssd_step``) it recomputed a Mamba-2 layer's ``where(fresh, 0,
    window)`` for the convolution AFTER the fusion that writes the step's new
    window into the same donated leaf: the first forward of every sync read a
    window one step on, in two of the seven layers, and ``correct`` read 0.35
    against a limit of 0.03 (my chip runs, PR 57; twelve layers deep nothing
    is rematerialised and nothing was wrong). ``Mamba2`` now hands that
    select through an ``optimization_barrier`` where it takes the kernel:
    every window leaf is read before anything writes it."""
    import json
    sds, _ = for_chip
    here = os.path.join(os.path.dirname(__file__), "..", "..", "..", "chipbench", "configs")
    with open(os.path.join(here, "nemotron-3-nano-30b-a3b.json")) as f:
        kinds = tuple(json.load(f)["overrides"]["layer_types"])
    model = _nemotron_share(kinds)
    abstract = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda a: sds(a.shape, dtype or a.dtype), tree)
    params = abstract(jax.eval_shape(model.init_params, jax.random.key(0)), jnp.bfloat16)
    pool = abstract(jax.eval_shape(lambda: model.init_cache(192, 4096)))
    text = _compile_state_pool_sync(sds, model, params, pool, 192, 4, 512, collect=True).as_text()
    assert text.count("dstpu_ssd_step") >= 14
    entry = text[text.index("ENTRY "):].splitlines()
    windows = re.findall(r"(pool_\d+__\d+_\.1): bf16\[192,1,3,6144\]", entry[0])
    assert len(windows) == 7
    for leaf in windows:
        uses = [(i, bool(re.match(r"\s*%[\w.\-]+ = \(?bf16\[192,1,3,6144\]", line)))
                for i, line in enumerate(entry) if re.search("%" + re.escape(leaf) + r"[,)]", line)]
        first_write = min((i for i, writes in uses if writes), default=len(entry))
        assert not [i for i, writes in uses if not writes and i > first_write], (leaf, uses)


@pytest.mark.parametrize("width", [2, pytest.param(512, marks=pytest.mark.slow)],
                         ids=["decode", "chunk"])
def test_exaone_moe_verify_and_draft_program(for_chip, width):
    """K-EXAONE-236B-A23B's sync at the published widths as the chip
    benchmark serves it (128 slots x 4096, ``steps_per_sync`` 4,
    ``prefill_chunk`` 512, experts 0-15 of 128, an eighth of the vocabulary,
    the device drafter on), with the dense layer under a window, one full
    expert layer and the multi-token-prediction module: every step verifies
    two columns a row over a 128-row ring (XLA's masked attention over [ring ;
    fresh rows]) and a row cache (the paged span kernel), decides the advance,
    runs the module over its own rows and drafts; the chunk sync adds the
    chunk as a (1, 512) forward over its own slot. The 256 rows x 8 pairs (16
    rows an expert) and the chunk's go by the dense product over the 16 held:
    no grouped product. The donated row caches are updated in place: no
    whole-leaf copy, scatter or transpose of one. A RING leaf (33.5 MB) is
    not so lucky: XLA's scatter of the two columns wants it position-major
    and the compiler relays it on the way into and out of every step (two
    copies a leaf in the loop's body: ``PERF.md`` section 7, for a
    ``perf_opt`` issue); the count is held where it is so that it does not
    grow. It fits with its temporaries beside the cell's 9.09 GB of weights
    and 4.56 GB of pool."""
    import types
    from deepspeed_tpu.inference.scheduler import DecodeScheduler
    sds, _ = for_chip
    slots, pool_len, steps = 128, 4096, 4
    base = get_model("k-exaone-236b-a23b")
    model = type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=2, layer_types=("full_attention", ) * 2,
        layer_windows=(128, 0), moe_experts_held=16, vocab_size=19200, max_seq_len=pool_len,
        attention_impl="flash"))
    abstract = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda a: sds(a.shape, dtype or a.dtype), tree)
    params = abstract(jax.eval_shape(model.init_params, jax.random.key(0)), jnp.bfloat16)
    pool = abstract(jax.eval_shape(lambda: model.init_cache(slots, pool_len)))
    shapes = sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(pool)})
    assert shapes == [(slots, 8, 128, 128), (slots, 8, pool_len, 128)]
    mock = types.SimpleNamespace(
        engine=types.SimpleNamespace(module=model, model_config=model.cfg), _shard_deg=1,
        _moe_stats=True, _moe=True, experts=None, _compiled={}, capacity=None,
        _pool_sharding=None, _draft_keeps_void=False)
    for name in ("_program", "_jit_step", "_moe_forward_stats", "_held_experts"):
        setattr(mock, name, types.MethodType(getattr(DecodeScheduler, name), mock))
    fn = DecodeScheduler._draft_fn(mock, False, False, steps, width)
    i32 = lambda *shape: sds(shape, jnp.int32)
    args = (params, pool, i32(slots, width), i32(slots), i32(slots), sds((slots, ), jnp.uint32),
            i32(slots), sds((slots, ), jnp.bool_), sds((slots, ), jnp.float32), i32(slots),
            sds((slots, ), jnp.float32)) + ((i32(4), ) if width > 2 else ())
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "dstpu_decode_attn" in text and "dstpu_kv_commit" in text
    assert "ragged-dot" not in text and "moe_experts" in text and "mtp_draft" in text
    assert "swa_attn" in text
    ring, rows = ("[" + ",".join(map(str, shape)) + "]" for shape in shapes)
    assert _pool_relayouts(text, rows) == (0, 0)
    in_loop, around = _pool_relayouts(text, ring)
    print(width, "ring leaf moves", in_loop, around)
    assert in_loop <= 4 and around <= 8, (in_loop, around)
    for kernel in ("[16,6144,2048]", "[16,2048,6144]"):
        assert _pool_relayouts(text, kernel) == (0, 0), kernel
    mem = compiled.memory_analysis()
    print(width, "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.4e9, mem
    assert 9.09e9 + 4.57e9 + mem.temp_size_in_bytes < 15.75 * 2**30, mem


def _compile_state_pool_sync(sds, model, params, pool, slots, steps, width, collect=False):
    """The scheduler's own sync (``DecodeScheduler._fused_fn`` on a mock that
    holds what the builder reads) of a model whose pool holds state, compiled
    for the described chip: ``steps`` forwards, the first ``width`` columns
    wide, greedy, or the collecting variant that ``correct`` runs."""
    import types
    from deepspeed_tpu.inference.scheduler import DecodeScheduler
    moe = bool(model.cfg.num_experts)
    mock = types.SimpleNamespace(
        engine=types.SimpleNamespace(module=model, model_config=model.cfg), _shard_deg=1,
        _fused_block=False, _moe_stats=moe, _moe=moe, experts=None, _compiled={},
        capacity=None, _pool_sharding=None, _state_pool=True,
        cache=types.SimpleNamespace(num_slots=slots))
    for name in ("_program", "_jit_step", "_moe_forward_stats", "_held_experts",
                 "_splits_chunk"):
        setattr(mock, name, types.MethodType(getattr(DecodeScheduler, name), mock))
    assert mock._splits_chunk(("fused", False, collect, width, steps)) is (width > 1)
    fn = DecodeScheduler._fused_fn(mock, False, collect, steps, width)
    i32 = lambda *shape: sds(shape, jnp.int32)
    args = (params, pool, i32(slots, width), i32(slots), i32(slots), sds((slots, ), jnp.uint32),
            i32(slots), sds((slots, ), jnp.bool_), sds((slots, ), jnp.float32), i32(slots),
            sds((slots, ), jnp.float32), i32(slots))
    return fn.lower(*args).compile()


@pytest.mark.parametrize("width, collect", [(1, False), (512, False), (512, True)],
                         ids=["decode", "chunk", "chunk-collecting"])
def test_lfm2_moe_step_program(for_chip, width, collect):
    """LFM2-8B-A1B's sync at the published widths as the chip benchmark serves
    it (the slots of the cell's own file x 4096, ``steps_per_sync`` 4,
    ``prefill_chunk`` 512, all 32 experts held, the whole vocabulary), the
    scheduler's own program
    (``DecodeScheduler._fused_fn``: greedy, and the collecting variant that
    ``correct`` runs) with a dense layer under a gated short convolution, an
    expert layer under attention and one under a convolution: the packed K/V
    leaf (head size 64) beside the convolutions' two carried rows a slot,
    per-head QK norm and rotation into the paged kernels at 4 query heads a
    key head, the slots x 4 pairs (slots / 8 rows an expert; the chunk's 512
    rows: 64) by the dense product over the 32 held (both widths multiples of
    256): no grouped product, the expert kernels read as they rest. The
    donated pool is updated in place: no whole-leaf copy of the K/V leaf in
    the loop or around it. It fits with its temporaries beside the cell's
    7.86 GB of weights and the 3.23 GB of pool that ISSUE 50's 128 slots
    would take (the host's delivery, not memory, holds the cell under 128)."""
    import json
    sds, _ = for_chip
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "..", "chipbench", "workloads",
                           "lfm2-8b-a1b.serve.reason-closed.json")) as f:
        serve = json.load(f)["serve"]
    slots, pool_len, steps = serve["num_slots"], serve["max_len"], serve["steps_per_sync"]
    assert (pool_len, steps, serve["prefill_chunk"]) == (4096, 4, 512) and 80 <= slots <= 128
    base = get_model("lfm2-8b-a1b")
    kinds = ("short_conv", "full_attention", "short_conv")
    model = type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=3, layer_types=kinds, moe_first_dense=1,
        max_seq_len=pool_len, attention_impl="flash"))
    abstract = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda a: sds(a.shape, dtype or a.dtype), tree)
    params = abstract(jax.eval_shape(model.init_params, jax.random.key(0)), jnp.bfloat16)
    pool = abstract(jax.eval_shape(lambda: model.init_cache(slots, pool_len)))
    shapes = sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(pool)})
    assert shapes == [(slots, 1, 2, 2048), (slots, 8, pool_len, 128)]
    compiled = _compile_state_pool_sync(sds, model, params, pool, slots, steps, width, collect)
    text = compiled.as_text()
    assert "dstpu_decode_attn" in text and "dstpu_kv_commit" in text
    assert "ragged-dot" not in text and "moe_experts" in text
    for scope in ("conv_proj", "conv_state", "conv_out"):
        assert scope in text, scope
    assert _pool_relayouts(text, "[" + ",".join(map(str, shapes[1])) + "]") == (0, 0)
    for kernel in ("[32,2048,1792]", "[32,1792,2048]"):
        assert _pool_relayouts(text, kernel) == (0, 0), kernel
    mem = compiled.memory_analysis()
    print(width, collect, "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.5e9, mem
    assert 7.86e9 + 3.23e9 + mem.temp_size_in_bytes < 15.75 * 2**30, mem


@pytest.mark.parametrize("width, collect", [
    (1, False), (512, False),
    pytest.param(512, True, marks=pytest.mark.slow)],  # ~40 s more; the chip run compiles it
    ids=["decode", "chunk", "chunk-collecting"])
def test_ling_hybrid_step_program(for_chip, width, collect):
    """Ling-3.0-flash's sync at the published widths as the chip benchmark
    serves it (the slots of the cell's own file x 4096, ``steps_per_sync`` 4,
    ``prefill_chunk`` 512, 128 of 512 experts held, a quarter of the
    vocabulary), the scheduler's own program (``DecodeScheduler._fused_fn``)
    with a dense layer under a Kimi-delta mixer, an expert layer under one and
    an expert layer under latent attention: the state leaf (32, 128, 128)
    beside the latent rows of 576 in one tree; the decode column's state
    update through the in-place kernel with a decay a key channel; the
    slots x 8 pairs by the dense product over the 128 held (both widths
    multiples of 256, 3 rows an expert): no grouped product. It fits with its
    temporaries beside the cell's 10.46 GB of weights and 2.20 GB of pool."""
    import json
    sds, _ = for_chip
    here = os.path.join(os.path.dirname(__file__), "..", "..", "..", "chipbench")
    with open(os.path.join(here, "workloads", "ling-3.0-flash.serve.reason-closed.json")) as f:
        serve = json.load(f)["serve"]
    with open(os.path.join(here, "configs", "ling-3.0-flash.json")) as f:
        overrides = json.load(f)["overrides"]
    slots, pool_len, steps = serve["num_slots"], serve["max_len"], serve["steps_per_sync"]
    assert (pool_len, steps, serve["prefill_chunk"]) == (4096, 4, 512) and 96 <= slots <= 192
    kinds = ("linear_attention", "linear_attention", "full_attention")
    model = get_model("ling-3.0-flash", **dict(
        overrides, dtype=jnp.bfloat16, num_layers=3, layer_types=kinds,
        moe_swiglu_limits=(0, ) * 3, moe_shared_swiglu_limits=(0, ) * 3,
        attention_impl="flash"))
    abstract = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda a: sds(a.shape, dtype or a.dtype), tree)
    params = abstract(jax.eval_shape(model.init_params, jax.random.key(0)), jnp.bfloat16)
    pool = abstract(jax.eval_shape(lambda: model.init_cache(slots, pool_len)))
    shapes = sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(pool)})
    assert shapes == [(slots, 1, 3, 12288), (slots, 1, 576, pool_len), (slots, 32, 128, 128)]
    compiled = _compile_state_pool_sync(sds, model, params, pool, slots, steps, width, collect)
    text = compiled.as_text()
    assert "dstpu_gdn_step" in text  # (a chunk's sync ends in decode substeps)
    assert "ragged-dot" not in text and "moe_experts" in text
    for scope in ("gdn_proj", "gdn_state", "gdn_out", "mla_proj", "mla_attn", "moe_router"):
        assert scope in text, scope
    assert _pool_relayouts(text, f"[{slots},32,128,128]") == (0, 0)
    # the latent leaf is carried in the one form it rests in, through the
    # column commit and the walk of every forward (six whole-leaf moves a
    # sync and 1.0 GB of temporaries for them, until PR 55)
    assert "dstpu_kv_commit_columns" in text
    assert _leaf_moves(text, (slots, 1, 576, pool_len)) == (0, 0)
    mem = compiled.memory_analysis()
    print(width, collect, "temporaries", mem.temp_size_in_bytes)
    assert 10.46e9 + 2.20e9 * slots / 192 + mem.temp_size_in_bytes < 15.75 * 2**30, mem
    if width == 1:
        assert mem.temp_size_in_bytes < 0.3e9, mem


@pytest.mark.parametrize("width", [1, 512], ids=["decode", "chunk"])
def test_falcon_h1_step_program(for_chip, width):
    """Falcon-H1-34B-Instruct's sync at the published widths as the chip
    benchmark serves it (the slots of the cell's own file x 4096,
    ``steps_per_sync`` 4, ``prefill_chunk`` 512, the whole vocabulary of
    261,120), the scheduler's own program (``DecodeScheduler._fused_fn``), two
    of its six two-mixer blocks deep: K/V rows AND Mamba-2 state in the SAME
    layer's slot, the decode column through ``dstpu_decode_attn`` and
    ``dstpu_kv_commit`` in groups of five query heads and the one-token state
    update at 32 x 128 x 256 in place (``dstpu_ssd_step``) under ``ssd_state``,
    both branches under ``hybrid_mixer``. It fits with its temporaries (the chunk's 512 x 261,120
    logits among them) beside the cell's 10.51 GB of weights and 4.04 GB of
    pool."""
    import json
    sds, _ = for_chip
    here = os.path.join(os.path.dirname(__file__), "..", "..", "..", "chipbench")
    with open(os.path.join(here, "workloads", "falcon-h1-34b.serve.reason-closed.json")) as f:
        serve = json.load(f)["serve"]
    slots, pool_len, steps = serve["num_slots"], serve["max_len"], serve["steps_per_sync"]
    assert (slots, pool_len, steps, serve["prefill_chunk"]) == (64, 4096, 4, 512)
    model = get_model("falcon-h1-34b-instruct", dtype=jnp.bfloat16, num_layers=2,
                      layer_types=("parallel_hybrid", ) * 2, max_seq_len=pool_len,
                      attention_impl="flash")
    abstract = lambda tree, dtype=None: jax.tree_util.tree_map(
        lambda a: sds(a.shape, dtype or a.dtype), tree)
    params = abstract(jax.eval_shape(model.init_params, jax.random.key(0)), jnp.bfloat16)
    pool = abstract(jax.eval_shape(lambda: model.init_cache(slots, pool_len)))
    assert [leaf.shape for leaf in (pool[0][0], pool[1][0], pool[2][0], pool[3][0])] == [
        (slots, 4, pool_len, 128), (slots, 4, pool_len, 128), (slots, 32, 128, 256),
        (slots, 1, 3, 5120)]
    compiled = _compile_state_pool_sync(sds, model, params, pool, slots, steps, width)
    text = compiled.as_text()
    for mark in ("dstpu_decode_attn", "dstpu_kv_commit", "dstpu_ssd_step", "hybrid_mixer",
                 "attn_proj", "ssd_proj", "ssd_state", "ssd_out", "lm_head"):
        assert mark in text, mark
    # the state leaf and the rows are carried in the one form they rest in
    # (the state leaf is 128 MiB, VMEM's size: not parked there either)
    assert _leaf_moves(text, (slots, 32, 128, 256)) == (0, 0)
    assert _pool_relayouts(text, f"[{slots},4,{pool_len},128]") == (0, 0)
    mem = compiled.memory_analysis()
    print(width, "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < (0.1e9 if width == 1 else 0.4e9), mem
    # six layers' temporaries are under three times two layers' (0.14 and 0.74
    # GB compiled six deep, 0.065 and 0.305 here: PERF.md section 4)
    assert 10.51e9 + 4.04e9 + 3 * mem.temp_size_in_bytes < 15.75 * 2**30, mem


def _accepted_cell_syncs():
    """(cell, model one period deep, slots, chunk, pool length, fused) of the
    serving cells the benchmark had before PR 39, as their tests above size
    them."""
    yield "gpt2-large.serve.chat-closed", _serving_model("gpt2-large"), 24, 64, 1024, True
    base = get_model("mistral-small-4-119b")
    yield "mistral-small-4-119b.serve.decode-closed", type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=1, moe_experts_held=32, vocab_size=32768,
        max_seq_len=8192, attention_impl="flash", scan_layers=False)), 64, 256, 2048, False
    base = get_model("olmo-hybrid-7b")
    yield "olmo-hybrid-7b.serve.decode-closed", type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=4, layer_types=base.cfg.layer_types[:4],
        max_seq_len=1024, attention_impl="flash")), 64, 128, 1024, False
    base = get_model("phi-4-mini-flash-reasoning")
    kinds = ("mamba", "diff_attention", "mamba", "diff_attention", "gmu", "cross_attention")
    yield "phi-4-mini-flash.serve.reason-closed", type(base)(dataclasses.replace(
        base.cfg, dtype=jnp.bfloat16, num_layers=6, layer_types=kinds,
        layer_windows=(0, 512, 0, 0, 0, 0), max_seq_len=4096,
        attention_impl="flash")), 64, 512, 4096, False


# sha256[:16] of the lowered sync (StableHLO text), column and chunk, taken at
# the parent commit 51fe9b3 with test_accepted_cells_lower_the_parents_programs'
# own arithmetic. A PR that means to change one of these programs replaces its
# digests; a PR that does not (a new model, a new layer kind) leaves them.
# PR 40 moved ONE: cell 4's chunk program (13426a2eaba288c8 at the parent),
# whose (1, 256) forward now takes the dense product over the 32 experts held
# (8 rows an expert: ``moe.layer.dense_held_pays``); its column is the parent's.
# PR 42 moved TWO, both of cell 5 (ef0b951382842c66 and 2b29bcdf4eabe0c7 at its
# parent 551c9a3): the state leaves rest packed, (64, 15, 96, 384), and the
# column's one-token update is ``dstpu_gdn_step`` (``ops/pallas/gdn_step.py``);
# the chunk program converts its slot's state around the scan. The other
# three cells hold no gated-delta layer and lower what they lowered.
# PR 55 moved TWO, both of cell 4 (7b6b3c73f810f160 and b75b19168934fbc2 at its
# parent e232eee): the latent leaf rests position-last, (64, 1, 320, 2048), its
# span commit is ``dstpu_kv_commit_columns`` (``ops/pallas/kv_commit.py``) in
# place of the scatter, and the block walk's two products read blocks of
# ``(B, D, blk)``. Cells 2, 5 and 6 hold no latent leaf and lower what they
# lowered.
PARENT_LOWERED = {
    "gpt2-large.serve.chat-closed": ("15065a760c93d007", "9bccfab4d863721b"),
    "mistral-small-4-119b.serve.decode-closed": ("64fc6e6f85e47a4b", "18ee3dd3cfc4bec7"),
    "olmo-hybrid-7b.serve.decode-closed": ("f887b9840c76ab25", "f1dfd33c486d64da"),
    "phi-4-mini-flash.serve.reason-closed": ("71e23c35b3433be3", "af018c91144275bb"),
}


def test_accepted_cells_lower_the_parents_programs(for_chip):
    """Cells 2, 4, 5 and 6 lower, one period deep at their published widths
    and the cells' shapes, the programs the parent lowers: a new layer kind,
    a restructured ``Block`` or a new router leaves them as they were. What
    is compared is the lowered text without the serialized bodies of the
    Pallas kernels: a body carries the absolute path and line of every
    frame that called it (``transformer.py``, this file), so it differs with
    the checkout's directory and with any line added above a call, whatever
    the kernel computes."""
    import hashlib
    sds, _ = for_chip
    strip = lambda text: re.sub(r'(\\22body\\22: \\22)[^\\]+(\\22)', r"\1\2", text)
    got = {}
    for name, model, slots, chunk, pool_len, fused in _accepted_cell_syncs():
        got[name] = tuple(
            hashlib.sha256(strip(_lower_sync(sds, model, slots, width, pool_len, fused)[0]
                                 .as_text()).encode()).hexdigest()[:16]
            for width in (1, chunk))
    assert got == PARENT_LOWERED
