"""The order a ZeRO-3 step states for its weight gathers
(``runtime/zero/gather_order.py``): on virtual CPU devices the barrier is an
identity and the gather a placement, so the ordered step computes what the
unordered one does; where nothing is sharded no barrier is traced."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models import get_model
from deepspeed_tpu.runtime.zero import gather_order
from deepspeed_tpu.runtime.zero.gather_order import GatherOrder, due_behind, ordered_product

HIDDEN, FFN, LAYERS, SEQ = 128, 512, 2, 64
THRESHOLD = 1000  # every kernel of the model is over it, no bias is
# what a layer's placed gathers bring in, as the step computes in float32:
# forward its six kernels (q, k, v, o, up, down), backward down_proj's
LAYER_BYTES = (4 * HIDDEN * HIDDEN + 2 * HIDDEN * FFN) * 4 + HIDDEN * FFN * 4
LAYER_GATHERS = 6 + 1


def opt_shaped(**over):
    """Two unrolled OPT-shaped layers (ReLU, pre-LayerNorm, learned
    positions, tied head), small."""
    over = {"scan_layers": False, **over}
    return get_model("tiny-gpt2", dtype=jnp.float32, activation="relu", hidden_size=HIDDEN,
                     intermediate_size=FFN, num_layers=LAYERS, **over)


def engine_for(model, stage, devices=4, telemetry=None):
    comm.initialize_mesh(devices=jax.devices()[:devices], data=devices)
    config = {"train_micro_batch_size_per_gpu": 2,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": stage,
                                    "stage3_param_persistence_threshold": THRESHOLD},
              "steps_per_print": 10**9, "seed": 3}
    if telemetry is not None:
        config["telemetry"] = {"enabled": True, "output_path": str(telemetry)}
    return deepspeed_tpu.initialize(model=model, config=config)[0]


def batch_for(engine):
    rng = np.random.default_rng(0)
    return {"input_ids": jnp.asarray(rng.integers(0, 256, (engine.train_batch_size(), SEQ)),
                                     jnp.int32)}


def micro(engine):
    return lambda params, batch: engine._micro_loss_and_grads(params, batch, None,
                                                              jnp.float32(1.0))


def barriers(engine):
    with engine.mesh:
        return str(jax.make_jaxpr(micro(engine))(engine.state.params,
                                                 batch_for(engine))).count("optimization_barrier")


def test_ordered_step_computes_the_unordered_steps_loss_and_gradients():
    out = {}
    for ordered in (False, True):
        engine = engine_for(opt_shaped(), 3)
        engine._loss_takes_order = ordered
        with engine.mesh:
            out[ordered] = jax.jit(micro(engine))(engine.state.params, batch_for(engine))
        comm._state["mesh"] = None
    (loss, grads), (loss_o, grads_o) = out[False], out[True]
    assert float(loss) == float(loss_o)  # the forward's products are the same ones
    flat, flat_o = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, grads_o))
    assert len(flat) == len(flat_o) == 4 + LAYERS * 16
    for (path, g), (_, g_o) in zip(flat, flat_o):
        # dW and dX are the same contractions in another order of operands
        np.testing.assert_allclose(np.asarray(g_o), np.asarray(g), rtol=1e-5, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def test_ordered_step_traces_its_barriers_and_counts_its_gathers():
    engine = engine_for(opt_shaped(), 3)
    before = len(gather_order.traced())
    # backward: down_proj's dX behind its dW; forward: k, v, o behind the
    # projection before them in both layers, layer 1's q behind layer 0's
    # attention and its up_proj behind layer 0's down_proj
    assert barriers(engine) == LAYERS + 3 * LAYERS + 2
    placed = dict(gather_order.traced()[before:])
    assert len(placed) == LAYER_GATHERS * LAYERS
    assert sum(placed.values()) == LAYERS * LAYER_BYTES
    assert [path for path, direction in placed if direction == "backward"] == [
        f"layer_{i}/mlp/down_proj/kernel" for i in range(LAYERS)]
    assert ("layer_1/mlp/up_proj/kernel", "forward") in placed


@pytest.mark.parametrize("case", ["stage0", "stage1", "stage2", "one_device", "scan_layers",
                                  "remat"])
def test_nothing_sharded_or_no_next_layer_traces_no_barrier(case):
    stage = {"stage0": 0, "stage1": 1, "stage2": 2}.get(case, 3)
    model = opt_shaped(**({"scan_layers": True} if case == "scan_layers" else
                          {"remat_policy": "nothing_saveable"} if case == "remat" else {}))
    engine = engine_for(model, stage, devices=1 if case == "one_device" else 4)
    before = len(gather_order.traced())
    assert barriers(engine) == 0
    assert gather_order.traced()[before:] == []
    if stage < 3 or case == "one_device":
        assert engine.planner.gathered_placements(engine.state.params) == {}


def test_a_forward_with_a_cache_traces_no_barrier():
    model = opt_shaped()
    comm.initialize_mesh(devices=jax.devices()[:4], data=4)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    plan = {"layer_0/mlp/up_proj/kernel": None, "layer_1/mlp/up_proj/kernel": None}
    cache = jax.eval_shape(lambda: model.init_cache(2, SEQ))

    def served(params, cache, ids):
        return model.module.apply({"params": params}, ids, kv_cache=cache, cache_index=0,
                                  gather_order=plan)
    text = str(jax.make_jaxpr(served)(params, cache, jnp.zeros((2, 8), jnp.int32)))
    assert "optimization_barrier" not in text


def test_planner_orders_only_what_zero3_alone_shards():
    from deepspeed_tpu.runtime.zero.sharding import ShardingPlanner
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    mesh = comm.initialize_mesh(devices=jax.devices()[:8], data=4, tensor=2)
    zero = DeepSpeedZeroConfig({"stage": 3, "stage3_param_persistence_threshold": THRESHOLD})
    params = {"layer_0": {"mlp": {"up_proj": {"kernel": jnp.zeros((HIDDEN, FFN)),
                                              "bias": jnp.zeros((FFN, ))},
                                  "down_proj": {"kernel": jnp.zeros((FFN, HIDDEN))}}}}
    plan = ShardingPlanner(mesh, zero, tp_rules=[(r"up_proj/kernel$", (None, "tensor"))]
                           ).gathered_placements(params)
    # the bias is under the threshold, a tensor-parallel rule splits up_proj
    assert list(plan) == ["layer_0/mlp/down_proj/kernel"]
    assert plan["layer_0/mlp/down_proj/kernel"].is_fully_replicated


def test_product_rule_orders_dx_behind_dw_and_keeps_the_shard():
    mesh = comm.initialize_mesh(devices=jax.devices()[:4], data=4)
    placement = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    x, w = jnp.ones((2, 8, 16)), jnp.full((16, 32), 0.5)

    def f(x, w, behind=True):
        return jnp.sum(ordered_product("...k,kn->...n", x, w, placement,
                                       dx_behind_dw=behind) ** 2)
    for behind in (True, False):
        jaxpr = jax.make_jaxpr(jax.grad(lambda x, w: f(x, w, behind), argnums=(0, 1)))(x, w)
        assert str(jaxpr).count("optimization_barrier") == int(behind)
    dx, dw = jax.grad(f, argnums=(0, 1))(x, w)
    want_dx, want_dw = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(dx, want_dx, rtol=1e-6)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-6)
    # what the backward keeps of the weight is the argument itself (the
    # shard), never the gathered copy: the residuals of the linearised
    # product are x and w
    _, vjp = jax.vjp(lambda x, w: ordered_product("...k,kn->...n", x, w, placement), x, w)
    kept = [leaf for leaf in jax.tree_util.tree_leaves(vjp) if hasattr(leaf, "shape")]
    assert sorted(leaf.shape for leaf in kept) == sorted([x.shape, w.shape])


def test_due_behind_ties_the_forward_and_lets_the_cotangents_pass():
    y, g = jnp.arange(4.0), {"a": jnp.ones((3, ))}

    def f(y):
        out, tied = due_behind(y * 2.0, g)
        return jnp.sum(out * out) + jnp.sum(tied["a"])
    assert str(jax.make_jaxpr(f)(y)).count("optimization_barrier") == 1
    assert str(jax.make_jaxpr(jax.grad(f))(y)).count("optimization_barrier") == 1
    np.testing.assert_allclose(jax.grad(f)(y), 8.0 * y)


def test_gather_order_hands_a_gathered_weight_to_its_product_once():
    mesh = comm.initialize_mesh(devices=jax.devices()[:4], data=4)
    placement = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    order = GatherOrder({"layer_1/mlp/up_proj/kernel": placement})
    assert order.sub("layer_0") is None and order.sub("layer_1").prefix == "layer_1/"
    w = jnp.ones((4, 8))
    y = order.due_behind(jnp.ones((2, 4)), {"layer_1/mlp/up_proj/kernel": w,
                                            "layer_1/mlp/down_proj/kernel": w.T})
    assert list(order.gathered) == ["layer_1/mlp/up_proj/kernel"]  # the plan holds no down_proj
    up = order.sub("layer_1").sub("mlp").sub("up_proj")
    np.testing.assert_allclose(up.product("bk,kn->bn", y, w, "kernel"), 4.0 * np.ones((2, 8)))
    assert order.gathered == {}


def test_engine_sets_the_gather_gauges(tmp_path):
    from deepspeed_tpu.telemetry import set_sink
    read = {}
    for devices in (4, 1):
        engine = engine_for(opt_shaped(), 3, devices, tmp_path / str(devices))
        try:
            for _ in range(2):  # the second step traces nothing and sets nothing
                engine.train_batch(batch={"input_ids": np.asarray(batch_for(engine)["input_ids"])})
            engine.telemetry.close()
        finally:
            set_sink(None)
        with open(engine.telemetry.jsonl_path) as f:
            gauges = [ev for ev in map(json.loads, f) if ev["type"] == "gauge"]
        read[devices] = {name: [ev["value"] for ev in gauges if ev["name"] == name]
                         for name in ("zero/param_gathers_pinned_per_step",
                                      "zero/param_gather_bytes_per_step")}
        comm._state["mesh"] = None
    assert read[4] == {"zero/param_gathers_pinned_per_step": [LAYER_GATHERS * LAYERS],
                       "zero/param_gather_bytes_per_step": [LAYERS * LAYER_BYTES]}
    assert read[1] == {"zero/param_gathers_pinned_per_step": [0],
                       "zero/param_gather_bytes_per_step": [0]}
