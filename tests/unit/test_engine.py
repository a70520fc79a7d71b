"""End-to-end engine tests (analogue of tests/unit/runtime/test_ds_initialize.py
and runtime/zero/test_zero.py correctness-vs-baseline pattern)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import comm

from .simple_model import SimpleModel, random_batch, random_dataset

HIDDEN = 64


def base_config(**over):
    cfg = {
        "train_batch_size": 16,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000,
    }
    cfg.update(over)
    return cfg


def make_engine(config, seed=0):
    model = SimpleModel(hidden_dim=HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config, rng_seed=seed)
    return engine


def train_losses(engine, steps=8, n_batches=2):
    losses = []
    for i in range(steps):
        batch = random_batch(engine.train_batch_size(), HIDDEN, seed=100 + i % n_batches)
        loss = engine.train_batch(batch=batch)
        losses.append(float(loss))
    return losses


def test_train_loss_decreases():
    engine = make_engine(base_config())
    losses = train_losses(engine, steps=10)
    assert losses[-1] < losses[0]
    assert engine.global_steps == 10


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_match_baseline(stage):
    """All ZeRO stages must be numerically equivalent to stage-0 DP."""
    comm._state["mesh"] = None
    baseline = train_losses(make_engine(base_config()), steps=5)
    comm._state["mesh"] = None
    cfg = base_config(zero_optimization={"stage": stage,
                                         "stage3_param_persistence_threshold": 0})
    stage_losses = train_losses(make_engine(cfg), steps=5)
    np.testing.assert_allclose(baseline, stage_losses, rtol=2e-4)


@pytest.mark.parametrize("stage", [2, 3])
def test_zero_stages_match_baseline_on_a_tied_vocabulary(stage):
    """The toy model above has no vocabulary projection. A tied model whose
    vocabulary takes the chunked cross-entropy, over ``data=4``: the head's
    weight gradient is summed over the chips once, behind the chunks, and
    the stage's shard is cut from that sum, so the stage's losses are stage
    0's only if the one sum is the whole gradient."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import get_model

    def losses(zero_stage):
        comm.initialize_mesh(devices=jax.devices()[:4], data=4)
        model = get_model("tiny", dtype=jnp.float32, vocab_size=4352, max_seq_len=65,
                          ce_chunk_size=16)
        assert model._use_chunked_ce() and model.cfg.tie_embeddings
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, rng_seed=0, config=base_config(
            train_batch_size=8, gradient_accumulation_steps=1,
            zero_optimization={"stage": zero_stage, "stage3_param_persistence_threshold": 0}))
        rng = np.random.default_rng(7)
        batches = [rng.integers(0, 4352, (8, 65)).astype(np.int32) for _ in range(2)]
        return [float(engine.train_batch(batch={"input_ids": batches[i % 2]})) for i in range(5)]

    baseline = losses(0)
    np.testing.assert_allclose(baseline, losses(stage), rtol=2e-4)
    assert baseline[-1] < baseline[0]


def test_zero3_params_are_sharded():
    cfg = base_config(zero_optimization={"stage": 3, "stage3_param_persistence_threshold": 0})
    engine = make_engine(cfg)
    kernel = engine.state.params["linear_0"]["kernel"]
    spec = kernel.sharding.spec
    assert any(s is not None for s in spec), f"stage-3 param not sharded: {spec}"
    # persistence threshold applies to COMPUTE params: above it they stay
    # replicated; master params stay sharded either way (ZeRO-1 semantics)
    comm._state["mesh"] = None
    cfg2 = base_config(zero_optimization={"stage": 3, "stage3_param_persistence_threshold": 10**9})
    engine2 = make_engine(cfg2)
    compute_spec = engine2.planner.param_spec("linear_0/kernel", (HIDDEN, HIDDEN))
    assert all(s is None for s in compute_spec)
    master_spec = engine2.planner.master_spec("linear_0/kernel", (HIDDEN, HIDDEN))
    assert any(s is not None for s in master_spec)


def test_facade_matches_fused():
    """forward/backward/step 3-call facade == fused train_batch numerics."""
    fused = train_losses(make_engine(base_config()), steps=3)

    comm._state["mesh"] = None
    engine = make_engine(base_config())
    gas = engine.gradient_accumulation_steps()
    micro = engine.train_micro_batch_size_per_gpu() * engine.dp_world_size()
    facade = []
    for i in range(3):
        batch = random_batch(engine.train_batch_size(), HIDDEN, seed=100 + i % 2)
        losses = []
        for g in range(gas):
            mb = {k: v[g * micro:(g + 1) * micro] for k, v in batch.items()}
            loss = engine.forward(mb)
            engine.backward(loss)
            losses.append(float(loss))
        engine.step()
        facade.append(float(np.mean(losses)))
    np.testing.assert_allclose(fused, facade, rtol=2e-4)


def test_fp16_loss_scaling():
    cfg = base_config(fp16={"enabled": True, "initial_scale_power": 8, "loss_scale_window": 2})
    engine = make_engine(cfg)
    losses = train_losses(engine, steps=6)
    assert np.isfinite(losses).all()
    assert float(engine.state.loss_scale.cur_scale) >= 256  # grew or held


def test_bf16_training():
    cfg = base_config(bf16={"enabled": True})
    engine = make_engine(cfg)
    losses = train_losses(engine, steps=6)
    assert losses[-1] < losses[0]


def test_checkpoint_roundtrip(tmp_path):
    """save → load → identical continued training (reference
    tests/unit/checkpoint pattern)."""
    engine = make_engine(base_config())
    train_losses(engine, steps=3)
    engine.save_checkpoint(str(tmp_path), tag="tag3")
    cont_a = train_losses(engine, steps=2)

    comm._state["mesh"] = None
    engine2 = make_engine(base_config(), seed=1)  # different init
    path, client_sd = engine2.load_checkpoint(str(tmp_path))
    assert client_sd["global_steps"] == 3
    assert engine2.global_steps == 3
    cont_b = train_losses(engine2, steps=2)
    np.testing.assert_allclose(cont_a, cont_b, rtol=1e-5)


def test_checkpoint_reshape_zero_stage(tmp_path):
    """Universal-checkpoint property: save at stage 0, resume at stage 3."""
    engine = make_engine(base_config())
    train_losses(engine, steps=2)
    engine.save_checkpoint(str(tmp_path))
    cont_a = train_losses(engine, steps=2)

    comm._state["mesh"] = None
    cfg = base_config(zero_optimization={"stage": 3, "stage3_param_persistence_threshold": 0})
    engine3 = make_engine(cfg, seed=1)
    engine3.load_checkpoint(str(tmp_path))
    cont_b = train_losses(engine3, steps=2)
    np.testing.assert_allclose(cont_a, cont_b, rtol=2e-4)


def test_lr_scheduler_in_step():
    cfg = base_config(scheduler={"type": "WarmupLR",
                                 "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-2,
                                            "warmup_num_steps": 10, "warmup_type": "linear"}})
    engine = make_engine(cfg)
    train_losses(engine, steps=2)
    lr = float(engine._last_metrics["lr"])
    assert 0 < lr < 1e-2  # still warming up


def test_dataloader_and_train_with_iter():
    ds = random_dataset(64, HIDDEN)
    model = SimpleModel(hidden_dim=HIDDEN)
    engine, _, loader, _ = deepspeed_tpu.initialize(model=model, config=base_config(),
                                                    training_data=ds)
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    it = iter(RepeatingLoader(loader))
    l0 = float(engine.train_batch(data_iter=it))
    l1 = float(engine.train_batch(data_iter=it))
    assert np.isfinite([l0, l1]).all()


def test_fused_path_carries_no_grad_acc_buffer():
    """The fused train_batch path must not allocate a param-sized grad
    accumulator (at 70B fp32 that's ~280 GB of dead HBM); only the 3-call
    facade materializes it."""
    import jax
    engine = make_engine(base_config())
    train_losses(engine, steps=2)
    assert jax.tree_util.tree_leaves(engine.state.grad_acc) == []
    # facade allocates lazily
    batch = random_batch(engine.train_batch_size() // 2, HIDDEN)
    engine.forward(batch)
    assert len(jax.tree_util.tree_leaves(engine.state.grad_acc)) > 0


def test_checkpoint_roundtrip_after_facade_use(tmp_path):
    """grad_acc is never checkpointed: save after facade use, resume fused."""
    engine = make_engine(base_config())
    gas = engine.gradient_accumulation_steps()
    micro = engine.train_micro_batch_size_per_gpu() * engine.dp_world_size()
    batch = random_batch(engine.train_batch_size(), HIDDEN, seed=100)
    for g in range(gas):
        mb = {k: v[g * micro:(g + 1) * micro] for k, v in batch.items()}
        engine.backward(engine.forward(mb))
    engine.step()
    engine.save_checkpoint(str(tmp_path))
    cont_a = train_losses(engine, steps=2)

    comm._state["mesh"] = None
    engine2 = make_engine(base_config(), seed=1)
    engine2.load_checkpoint(str(tmp_path))
    cont_b = train_losses(engine2, steps=2)
    np.testing.assert_allclose(cont_a, cont_b, rtol=1e-5)


def test_shard_batch_rejects_non_divisible_batch():
    """A batch not divisible by the DP degree must error, not silently
    replicate (losing data parallelism)."""
    engine = make_engine(base_config())  # dp = 8 on the virtual mesh
    with pytest.raises(ValueError, match="not divisible"):
        engine.eval_batch(random_batch(3, HIDDEN))


def test_induced_fp16_overflow_skips_step():
    """An actual inf gradient must skip the update, halve the scale, and
    count the skipped step (reference DynamicLossScaler semantics)."""
    cfg = base_config(fp16={"enabled": True, "initial_scale_power": 16})
    engine = make_engine(cfg)
    params_before = np.asarray(engine.state.params["head"]["kernel"])
    scale_before = float(engine.state.loss_scale.cur_scale)
    bad = random_batch(engine.train_batch_size(), HIDDEN, seed=0)
    bad["y"] = np.full_like(bad["y"], 1e25)  # (pred - 1e25)^2 -> inf in fp32
    engine.train_batch(batch=bad)
    assert int(engine.state.skipped_steps) == 1
    assert int(engine.state.step) == 0
    assert float(engine.state.loss_scale.cur_scale) <= scale_before
    np.testing.assert_array_equal(np.asarray(engine.state.params["head"]["kernel"]), params_before)
    # recovery: clean batches train normally afterwards
    losses = train_losses(engine, steps=2)
    assert np.isfinite(losses).all()


def test_loss_scale_window_semantics():
    """Scale doubles after exactly `scale_window` clean updates, not one early."""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.fp16.loss_scaler import DynamicLossScaler
    scaler = DynamicLossScaler(init_scale=2.0**8, scale_window=4, delayed_shift=2)
    state = scaler.init_state()
    clean = jnp.asarray(False)
    for i in range(3):
        state = scaler.update(state, clean)
        assert float(state.cur_scale) == 2.0**8, f"doubled early at update {i + 1}"
    state = scaler.update(state, clean)  # 4th clean update
    assert float(state.cur_scale) == 2.0**9
    # overflow resets the window
    state = scaler.update(state, jnp.asarray(True))
    state = scaler.update(state, jnp.asarray(True))  # hysteresis spent -> halve
    assert float(state.cur_scale) == 2.0**8


def test_activation_checkpointing_config_applies_remat():
    """The activation_checkpointing section must change the model (remat
    policy), and remat must not change numerics."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import get_model

    def run(cfg_over):
        comm._state["mesh"] = None
        model = get_model("tiny", dtype=jnp.float32)
        cfg = {"train_batch_size": 8, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "steps_per_print": 1000, **cfg_over}
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg, rng_seed=0)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, 256, (8, 32)).astype(np.int32)}
        return model, [float(engine.train_batch(batch=batch)) for _ in range(2)]

    m_base, base = run({})
    assert m_base.cfg.remat_policy is None
    m_ac, ac = run({"activation_checkpointing": {"policy": "nothing_saveable"}})
    assert m_ac.cfg.remat_policy == "nothing_saveable"
    np.testing.assert_allclose(base, ac, rtol=2e-4)
    # HF-style boolean alias
    m_gc, _ = run({"gradient_checkpointing": True})
    assert m_gc.cfg.remat_policy == "nothing_saveable"


def test_async_checkpoint_save(tmp_path):
    """checkpoint.async_save plumbs through; 'latest' appears only after the
    write is durable and the checkpoint loads back identically."""
    cfg = base_config(checkpoint={"async_save": True})
    engine = make_engine(cfg)
    train_losses(engine, steps=2)
    engine.save_checkpoint(str(tmp_path), tag="async_tag")
    cont_a = train_losses(engine, steps=2)  # overlaps the background commit
    engine.wait_checkpoint_saves()
    assert (tmp_path / "latest").read_text().strip() == "async_tag"

    comm._state["mesh"] = None
    engine2 = make_engine(base_config(), seed=1)
    engine2.load_checkpoint(str(tmp_path))
    cont_b = train_losses(engine2, steps=2)
    np.testing.assert_allclose(cont_a, cont_b, rtol=1e-5)


def test_inert_config_section_warns(caplog):
    import logging
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.utils.logging import logger as ds_logger
    ds_logger.propagate = True  # let caplog's root handler see records
    try:
        with caplog.at_level(logging.WARNING, logger="DeepSpeedTPU"):
            DeepSpeedConfig({"train_batch_size": 8, "amp": {"enabled": True}}, world_size=1)
        assert any("amp" in r.message and "NO effect" in r.message for r in caplog.records)
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="DeepSpeedTPU"):
            DeepSpeedConfig({"train_batch_size": 8, "amp": {}}, world_size=1)
        assert not any("amp" in r.message for r in caplog.records)
    finally:
        ds_logger.propagate = False


def test_client_optimizer_and_scheduler():
    import optax
    model = SimpleModel(hidden_dim=HIDDEN)
    sched = deepspeed_tpu.WarmupDecayLR(total_num_steps=100, warmup_max_lr=1e-2, warmup_num_steps=5)
    engine, _, _, lr_sched = deepspeed_tpu.initialize(
        model=model, config={"train_batch_size": 16},
        optimizer=optax.adam(1e-2), lr_scheduler=sched)
    assert lr_sched is sched
    losses = train_losses(engine, steps=4)
    assert losses[-1] < losses[0]


def test_checkpoint_restore_different_mesh_shape(tmp_path):
    """Universal-checkpoint property across MESH shapes (not just ZeRO
    stages): save on a tp=2 x dp=4 mesh, resume on a dp=8 mesh."""
    comm._state["mesh"] = None
    cfg_tp = base_config(mesh={"tensor_parallel_size": 2})
    engine = make_engine(cfg_tp)
    train_losses(engine, steps=2)
    engine.save_checkpoint(str(tmp_path))
    cont_a = train_losses(engine, steps=2)

    comm._state["mesh"] = None
    engine2 = make_engine(base_config(), seed=1)  # dp=8, no tp
    engine2.load_checkpoint(str(tmp_path))
    cont_b = train_losses(engine2, steps=2)
    np.testing.assert_allclose(cont_a, cont_b, rtol=2e-4)


def test_multiprocess_smoke(tmp_path):
    """Two real JAX processes over the distributed coordinator run one DP
    step each and agree on the loss (the multi-host path of _shard_batch /
    make_array_from_process_local_data)."""
    import subprocess, sys, os
    script = tmp_path / "worker.py"
    script.write_text("""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
import deepspeed_tpu
sys.path.insert(0, os.environ["DSTPU_TESTS"])
from unit.simple_model import SimpleModel, random_batch

deepspeed_tpu.init_distributed()
assert jax.process_count() == 2, jax.process_count()
model = SimpleModel(hidden_dim=32)
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model, config={"train_batch_size": 8,
                         "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                         "steps_per_print": 1000}, rng_seed=0)
full = random_batch(8, 32, seed=0)
share = 8 // jax.process_count()
pid = jax.process_index()
mine = {k: v[pid * share:(pid + 1) * share] for k, v in full.items()}
loss = float(engine.train_batch(batch=mine))
print(f"WORKER{pid} LOSS {loss:.6f}", flush=True)
""")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    tests_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo_root = os.path.dirname(tests_dir)
    env["DSTPU_TESTS"] = tests_dir
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    port = 23456 + os.getpid() % 1000
    procs = []
    for pid in range(2):
        e = dict(env, COORDINATOR_ADDRESS=f"127.0.0.1:{port}", JAX_NUM_PROCESSES="2",
                 JAX_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen([sys.executable, str(script)], env=e,
                                      stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                                      text=True))
    outs = [p.communicate(timeout=420)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
    losses = sorted(line.split()[-1] for out in outs for line in out.splitlines()
                    if "LOSS" in line)
    assert len(losses) == 2 and losses[0] == losses[1], losses
