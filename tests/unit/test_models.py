"""Model family tests: training under every parallelism layout must be
numerically equivalent (the TPU analogue of reference zero-vs-baseline
correctness tests)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models import get_model, available_models


def ids_batch(b=8, t=64, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, (b, t)).astype(np.int32)}


def run_losses(model_name, mesh_cfg=None, zero_stage=0, steps=3, **model_kw):
    comm._state["mesh"] = None
    model = get_model(model_name, dtype=jnp.float32, **model_kw)
    cfg = {"train_batch_size": 8, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "steps_per_print": 1000, "zero_optimization": {"stage": zero_stage}}
    if mesh_cfg:
        cfg["mesh"] = mesh_cfg
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg, rng_seed=0)
    batch = ids_batch()
    return [float(engine.train_batch(batch=batch)) for _ in range(steps)]


def test_tiny_trains():
    losses = run_losses("tiny", steps=5)
    assert losses[-1] < losses[0]


def test_layout_equivalence_dense():
    """DP / ZeRO-3 / TP2 / TP4 all produce identical losses."""
    base = run_losses("tiny")
    assert np.allclose(base, run_losses("tiny", zero_stage=3), rtol=1e-5)
    assert np.allclose(base, run_losses("tiny", mesh_cfg={"tensor_parallel_size": 2}), rtol=1e-4)
    assert np.allclose(base, run_losses("tiny", mesh_cfg={"tensor_parallel_size": 4},
                                        zero_stage=1), rtol=1e-4)


def test_layout_equivalence_moe():
    """MoE: DP-only == expert-parallel == EP x TP."""
    base = run_losses("tiny-moe")
    assert np.allclose(base, run_losses("tiny-moe", mesh_cfg={"expert_parallel_size": 4}), rtol=1e-4)
    assert np.allclose(base, run_losses("tiny-moe", mesh_cfg={"expert_parallel_size": 2,
                                                              "tensor_parallel_size": 2}), rtol=1e-4)


@pytest.mark.parametrize("model_name,mesh_cfg,zero_stage", [
    ("tiny", {"tensor_parallel_size": 2}, 0),
    ("tiny", {"tensor_parallel_size": 2}, 3),
    ("tiny-moe", {"expert_parallel_size": 4}, 2),
    ("tiny-moe", {"expert_parallel_size": 2, "tensor_parallel_size": 2}, 0),
])
def test_layout_equivalence_chunked_vocabulary(model_name, mesh_cfg, zero_stage):
    """The layouts above run a 256-word vocabulary through dense logits. At
    4,352 words the chunked cross-entropy sums the head's weight gradient by
    chip and across the data-parallel axes (``expert`` and ``data``) once:
    under ``tensor`` and ``expert`` axes the losses must still be plain DP's."""
    kw = dict(vocab_size=4352, ce_chunk_size=32)
    assert get_model(model_name, **kw)._use_chunked_ce()
    assert np.allclose(run_losses(model_name, **kw),
                       run_losses(model_name, mesh_cfg=mesh_cfg, zero_stage=zero_stage, **kw),
                       rtol=1e-4)


def test_moe_trains():
    losses = run_losses("tiny-moe", steps=5)
    assert losses[-1] < losses[0]


def test_gqa_and_families():
    # gpt2 family (learned pos, layernorm, gelu) and llama family (rope,
    # rmsnorm, swiglu, gqa) both train
    l1 = run_losses("tiny", steps=2)  # llama-style incl. GQA (4 heads, 2 kv)
    assert np.isfinite(l1).all()
    comm._state["mesh"] = None
    model = get_model("gpt2-125m", dtype=jnp.float32, num_layers=2, hidden_size=64,
                      num_heads=4, vocab_size=256, max_seq_len=128)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config={"train_batch_size": 8, "steps_per_print": 1000,
                             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    batch = ids_batch()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(3)]
    assert losses[-1] < losses[0]


def test_labels_and_masking():
    model = get_model("tiny", dtype=jnp.float32)
    params = model.init_params(jax.random.key(0))
    batch = ids_batch(4, 32)
    # explicit labels with ignore_index
    labels = np.roll(batch["input_ids"], -1, axis=1)
    labels[:, -1] = -100
    loss_a = model.loss(params, {"input_ids": batch["input_ids"], "labels": labels}, None)
    # default shift path uses same target tokens (minus last position)
    loss_b = model.loss(params, batch, None)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)


def test_scan_vs_unrolled():
    """nn.scan layer stacking must equal the unrolled model."""
    comm._state["mesh"] = None
    m_scan = get_model("tiny", dtype=jnp.float32, scan_layers=True)
    m_unroll = get_model("tiny", dtype=jnp.float32, scan_layers=False)
    rng = jax.random.key(0)
    p_scan = m_scan.init_params(rng)
    p_unroll = m_unroll.init_params(rng)
    # copy scanned params (leading L dim) into the unrolled tree
    def strip(tree, i):
        return jax.tree_util.tree_map(lambda x: x[i], tree)
    p_unroll = dict(p_unroll)
    for i in range(2):
        p_unroll[f"layer_{i}"] = strip(p_scan["layers"], i)
    for k in ("embed", "final_norm", "lm_head"):
        if k in p_scan:
            p_unroll[k] = p_scan[k]
    batch = ids_batch(2, 32)
    la = m_scan.loss(p_scan, batch, None)
    lb = m_unroll.loss(p_unroll, batch, None)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)


def test_presets_resolve():
    for name in available_models():
        from deepspeed_tpu.models import _PRESETS
        if name == "ling-3.0-flash":
            # refused WHOLE by name (its top layers' clamped activations), served cut
            with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
                _PRESETS[name]()
            continue
        cfg = _PRESETS[name]()
        assert cfg.num_params() > 0
    # spot-check published sizes
    from deepspeed_tpu.models import _PRESETS
    assert 100e6 < _PRESETS["gpt2-125m"]().num_params() < 180e6
    assert 7e9 < _PRESETS["llama3-8b"]().num_params() < 9e9
    assert 65e9 < _PRESETS["llama3-70b"]().num_params() < 75e9


def test_remat_policy():
    losses_remat = run_losses("tiny", steps=2, remat_policy="nothing_saveable")
    losses_base = run_losses("tiny", steps=2)
    np.testing.assert_allclose(losses_remat, losses_base, rtol=1e-5)
