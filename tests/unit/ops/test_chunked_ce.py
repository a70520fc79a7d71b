"""Chunked cross-entropy numerics vs the dense optax reference (pattern:
reference tests/unit/ops kernel-vs-torch tolerance asserts).

The chunked path never materializes the full (B, T, V) logits; forward and
hand-written backward must still match the dense computation bit-for-bit in
fp32 up to reduction order. Where the batch is sharded over data-parallel
devices the head's weight gradient crosses them ONCE a step, whatever the
chunk count: counted in the compiled ZeRO step on the CPU mesh.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models import get_model
from deepspeed_tpu.models.transformer import _ce_batch_axes, chunked_cross_entropy


def make_case(B=4, T=100, H=32, V=999, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, T, H)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    valid = jnp.asarray(rng.random((B, T)) > 0.1)
    return x, labels, valid, V


def dense_reference(x, w, labels, valid, transpose):
    eq = "bth,vh->btv" if transpose else "bth,hv->btv"
    logits = jnp.einsum(eq, x, w).astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    return 3.5 * jnp.sum(ce * valid)  # non-unit cotangent exercises g


@pytest.mark.parametrize("transpose", [True, False])
@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_matches_dense_reference(transpose, chunk):
    x, labels, valid, V = make_case()
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=((V, 32) if transpose else (32, V))) * 0.1, jnp.float32)

    def new(x, w):
        return 3.5 * chunked_cross_entropy(x, w, labels, valid, chunk=chunk, transpose=transpose)

    r, gr = jax.value_and_grad(dense_reference, argnums=(0, 1))(x, w, labels, valid, transpose)
    n, gn = jax.value_and_grad(new, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(r), float(n), rtol=1e-6)
    for a, b in zip(gr, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_all_positions_masked():
    x, labels, valid, V = make_case(T=64)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(V, 32)) * 0.1, jnp.float32)
    none_valid = jnp.zeros_like(valid)
    total = chunked_cross_entropy(x, w, labels, none_valid, chunk=32, transpose=True)
    assert float(total) == 0.0
    g = jax.grad(lambda x: chunked_cross_entropy(x, w, labels, none_valid, chunk=32,
                                                 transpose=True))(x)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_model_auto_threshold():
    """tiny (V=256) uses dense logits; a >=4k-vocab config uses the chunked
    path; ce_chunk_size=0 forces dense."""
    assert not get_model("tiny")._use_chunked_ce()
    assert get_model("tiny", vocab_size=8192)._use_chunked_ce()
    assert not get_model("tiny", vocab_size=8192, ce_chunk_size=0)._use_chunked_ce()


# ---- the batch sharded over data-parallel devices -------------------------
VOCAB, WIDTH = 4352, 64  # no other leaf of the tiny model has a 4352


@pytest.mark.parametrize("rests", ["by_chip", "whole"])
@pytest.mark.parametrize("chunks", [1, 2, 4])
@pytest.mark.parametrize("transpose", [True, False])
@pytest.mark.parametrize("dp", [1, 2, 4])
def test_sharded_batch_matches_dense_reference(dp, transpose, chunks, rests):
    """Loss and BOTH gradients, the batch over ``data=dp``: the per-chip
    partial sums and their one sum are the whole gradient. The backward
    takes the batch's axes from the mesh (``_ce_batch_axes``), so a batch
    that rests whole on every chip has to give the same gradient."""
    mesh = comm.initialize_mesh(devices=jax.devices()[:dp], data=dp)
    x, labels, valid, V = make_case(T=128, V=1000)
    w = jnp.asarray(np.random.default_rng(1).normal(size=((V, 32) if transpose else (32, V))) * 0.1,
                    jnp.float32)
    rows = NamedSharding(mesh, P("data") if rests == "by_chip" else P())
    x, labels, valid = (jax.device_put(a, rows) for a in (x, labels, valid))
    assert _ce_batch_axes(x.shape[0]) == ((("data", ), dp) if dp > 1 else ((), 1))

    def new(x, w):
        return 3.5 * chunked_cross_entropy(x, w, labels, valid, chunk=128 // chunks,
                                           transpose=transpose)

    with mesh:
        n, gn = jax.jit(jax.value_and_grad(new, argnums=(0, 1)))(x, w)
    r, gr = jax.value_and_grad(dense_reference, argnums=(0, 1))(x, w, labels, valid, transpose)
    np.testing.assert_allclose(float(r), float(n), rtol=1e-6)
    for a, b in zip(gr, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_one_chip_keeps_the_plain_backward():
    """At ``dp`` = 1 the backward is the one it was: the program traced on a
    one-device mesh is the one traced with no mesh at all, with no sharding
    constraint in it, and nothing is tallied for the links."""
    x, labels, valid, V = make_case(T=64)
    w = jnp.zeros((V, 32), jnp.float32)

    def traced():
        return str(jax.make_jaxpr(jax.grad(
            lambda x, w: chunked_cross_entropy(x, w, labels, valid, chunk=32, transpose=True),
            argnums=(0, 1)))(x, w))

    no_mesh = traced()
    comm.initialize_mesh(devices=jax.devices()[:1], data=1)
    before, _ = comm.traced_head_grad()
    assert traced() == no_mesh and "sharding_constraint" not in no_mesh
    assert comm.traced_head_grad() == (before + 1, (0, 0))
    comm.initialize_mesh(devices=jax.devices()[:4], data=4)
    assert "sharding_constraint" in traced()
    assert comm.traced_head_grad() == (before + 2, (1, V * 32 * 4))


def tied_engine(stage, seq, chunk, tmp_path=None):
    """A tied tiny model whose vocabulary takes the chunked path, on
    ``data=4`` of the CPU mesh."""
    comm.initialize_mesh(devices=jax.devices()[:4], data=4)
    model = get_model("tiny", dtype=jnp.float32, vocab_size=VOCAB, hidden_size=WIDTH,
                      max_seq_len=seq, ce_chunk_size=chunk)
    assert model._use_chunked_ce() and model.cfg.tie_embeddings
    config = {"train_batch_size": 8, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": stage, "stage3_param_persistence_threshold": 0},
              "steps_per_print": 10**9}
    if tmp_path is not None:
        config["telemetry"] = {"enabled": True, "output_path": str(tmp_path / "tel")}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, rng_seed=0, config=config)
    return engine


def head_gradient_reductions(text):
    """Vocabulary-sized operands from ``loss_ce`` that the compiled step's
    cross-device reductions carry. The CPU pipeline combines the step's
    all-reduces into a few tuples under ONE operation's name, so an operand
    is placed by the instruction that made it (the lookup's scatter-add, the
    other half of a tied gradient, is not the cross-entropy's)."""
    made_by = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = .*?op_name=\"([^\"]*)\"", text, re.M))
    found = []
    for line in text.splitlines():
        m = re.search(r"= (.*?) (?:all-reduce|reduce-scatter)(?:-start)?\((.*?)\)", line)
        if m:
            shapes = re.findall(r"[a-z0-9]+\[[0-9,]*\]", m.group(1))
            operands = re.findall(r"%[\w.-]+", m.group(2))
            own = re.search(r"op_name=\"([^\"]*)\"", line).group(1)
            found += [shape for shape, operand in zip(shapes, operands)
                      if str(VOCAB) in re.findall(r"[0-9]+", shape)
                      and "loss_ce" in made_by.get(operand, own)]
    return found


@pytest.mark.parametrize("chunks", [2, 4])
@pytest.mark.parametrize("stage", [0, 2, 3])
def test_compiled_step_reduces_the_head_gradient_once(stage, chunks):
    seq = 129  # 128 shifted positions
    engine = tied_engine(stage, seq, 128 // chunks)
    batch = engine._shard_batch({"input_ids": np.zeros((1, 8, seq), np.int32)},
                                leading_scan_dim=True)
    with engine.mesh:
        text = engine._build_train_batch_fn().lower(engine.state, batch).compile().as_text()
    assert "loss_ce" in text
    # each chip's own float32 sum, once, at every stage (the parent's step
    # held one bf16-rounded product a chunk)
    assert head_gradient_reductions(text) == [f"f32[{VOCAB},{WIDTH}]"]


def test_engine_sets_the_head_gradient_gauges(tmp_path):
    """A step that traced its program says what its cross-entropy asks of
    the links: one float32 sum of the head's gradient a step."""
    from deepspeed_tpu.telemetry import set_sink
    engine = tied_engine(3, 129, 32, tmp_path)
    try:
        rng = np.random.default_rng(0)
        for _ in range(2):  # the second step traces nothing and sets nothing
            engine.train_batch(batch={"input_ids": rng.integers(0, VOCAB, (8, 129)).astype(np.int32)})
        engine.telemetry.close()
    finally:
        set_sink(None)
    with open(engine.telemetry.jsonl_path) as f:
        gauges = [ev for ev in map(json.loads, f) if ev["type"] == "gauge"]
    for name, want in (("zero/head_grad_reductions_per_step", 1),
                       ("zero/head_grad_reduced_bytes_per_step", VOCAB * WIDTH * 4)):
        assert [ev["value"] for ev in gauges if ev["name"] == name] == [want]
