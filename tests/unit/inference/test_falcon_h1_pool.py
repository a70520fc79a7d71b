"""A ``falcon_h1`` stack (``layer_types`` of ``parallel_hybrid``: every block a
Mamba-2 mixer AND grouped-query attention on one normed input, each scaled by
a published constant, then a SwiGLU; TII Falcon-H1's kind) at a small size on
the CPU in float32: the program against the plain reference
(``chipbench/references/falcon_h1.py``: one causal forward, no cache, the
recurrence token by token), each published constant seen, the slot pool's
span programs over K/V rows AND Mamba-2 state in the SAME layer, the
refusals, the counters of both mixers' required work, the parameter trees and
the sizes of the published preset.

Weights: the benchmark's own draw (``serve_falcon_h1.falcon_params``) with
biases and norm scales moved off 0 and 1, so that a dropped bias or scale
shows. ``TOL``: the reference's float32 limit, 1e-5; the served path reads
1e-6 at worst; a wrong state, span, weight or constant gives 1e-3 and up."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import get_model

from . import _ladder
from ._serving import VOCAB
from ._serving import prompts as _prompts

NAME = "tiny-falcon-h1"
# the tiny twin's sizes and constants, as the rehearsal fixture publishes them:
# every constant off 1, no two alike
_, HP, TOL = _ladder.reference(NAME)


@pytest.fixture(scope="module")
def tiny():
    return _ladder.built(NAME)


def _compared(model, params, ids, hp=HP):
    return _ladder.agrees(NAME, model, params, ids, hp)


class TestLadder(_ladder.Ladder):
    twin = NAME

    def test_full_forward_matches_the_reference(self):
        """70 positions: eight Mamba-2 chunks of 8 and a partial one; 5 query
        heads read ONE key/value head (the published odd group of five)."""
        cfg = _ladder.built(NAME)[0].cfg
        assert (cfg.num_heads, cfg.kv_heads) == (5, 1)
        super().test_full_forward_matches_the_reference()

    def served_pool(self, case, sched):
        """With the kernels the decode column goes through ``dstpu_decode_attn``
        and ``dstpu_kv_commit`` in groups of five."""
        assert (sched.kv_commit_programs["inplace"] > 0) is case["kernels"]


# each published constant, moved alone: the program with that ONE constant at
# another value must leave the reference's limit (fourteen numbers: seven
# scalars, the MLP's two, ssm_multipliers' five)
_MOVED = [(name, {name: 2.0 if name == "attention_in_multiplier" else 1.0}) for name in (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    "mlp_gate_multiplier", "mlp_down_multiplier")] + [
    (f"ssm_multipliers[{i}]", {"ssm_multipliers": tuple(
        1.0 if j == i else m for j, m in enumerate(HP["ssm_multipliers"]))}) for i in range(5)]


_IDS = np.random.RandomState(2).randint(0, VOCAB, (1, 24))


@pytest.fixture(scope="module")
def one_layer():
    """The twin one block deep: every constant acts in it, and a case costs a
    quarter of the stack's."""
    model = get_model("tiny-falcon-h1", dtype=jnp.float32, num_layers=1,
                      layer_types=("parallel_hybrid", ))
    params = _ladder.params_of(NAME, model, seed=11)
    assert _compared(model, params, _IDS)["ok"]  # unmoved, the program agrees
    return model, params


@pytest.mark.parametrize("moved", [m for _, m in _MOVED], ids=[n for n, _ in _MOVED])
def test_each_published_constant_is_seen(one_layer, moved):
    model, params = one_layer
    assert all(getattr(model.cfg, k) != v for k, v in moved.items())
    other = type(model)(dataclasses.replace(model.cfg, **moved))
    res = _compared(other, params, _IDS)
    assert not res["ok"] and res["error"] > 3 * TOL, res["error"]


def test_a_layers_slot_holds_rows_and_state(tiny):
    """``rows, rows, state, state`` in every layer, in the component-major
    tree; the pool's accounting takes bytes a token and bytes a slot from the
    same layers."""
    model, _ = tiny
    kinds = model.cache_kinds()
    assert [tuple(k[i] for k in kinds) for i in range(4)] == [
        ("rows", "rows", "state", "state")] * 4
    pool = model.init_cache(3, 32)
    assert [leaf.shape for leaf in (pool[0][0], pool[1][0], pool[2][0], pool[3][0])] == [
        (3, 1, 32, 16), (3, 1, 32, 16), (3, 4, 8, 16), (3, 1, 3, 96)]
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    kv = SlotKVCache(pool, 3, 32, kinds=kinds)
    assert kv.bytes_per_token() == 4 * 2 * 16 * 4
    assert kv.state_bytes_per_slot() == 4 * (4 * 8 * 16 + 3 * 96) * 4


def test_what_the_kind_refuses(tiny):
    model, params = tiny
    cfg = model.cfg
    hybrid = ("parallel_hybrid", ) * 4
    with pytest.raises(ValueError, match="composes with no other kind"):
        dataclasses.replace(cfg, layer_types=("parallel_hybrid", ) * 3 + ("full_attention", ))
    for bad in ({"layer_windows": (8, 0, 0, 0)}, {"post_norm": True},
                {"num_experts": 4, "moe_dropless": True}, {"pos_embedding": "alibi"}):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, layer_types=hybrid, **bad)
    with pytest.raises(ValueError, match="float dtype"):
        dataclasses.replace(cfg, int8_weights=True)
    with pytest.raises(ValueError, match="parallel_hybrid"):
        dataclasses.replace(cfg, mtp_layers=1)
    with pytest.raises(ValueError, match="ssm_num_heads"):
        dataclasses.replace(cfg, ssm_groups=3)
    with pytest.raises(ValueError, match="five constants"):
        dataclasses.replace(cfg, ssm_multipliers=(1.0, 2.0))
    with pytest.raises(ValueError, match="applied by a stack of parallel_hybrid"):
        dataclasses.replace(get_model("tiny").cfg, key_multiplier=0.5)
    with pytest.raises(NotImplementedError, match="no int8 tier"):
        model.init_cache(2, 64, quantized=True)
    with pytest.raises(NotImplementedError, match="span programs"):
        model.apply_with_cache(params, jnp.zeros((2, 4), jnp.int32), model.init_cache(2, 64), 0)
    with pytest.raises(ValueError, match="served in its float dtype"):
        deepspeed_tpu.init_inference(model, config={"dtype": "int8"}, params=params)
    sched = _ladder.engine(NAME).scheduler()
    assert any("parallel_hybrid" in r for r in sched._fused_block_reasons)


def test_counters_count_both_mixers_of_a_layer(tiny, tmp_path):
    """Hand-counted: one request of 20 prompt tokens, chunk 16, K = 4, alone
    in the pool; 4 layers, each counted in the state family AND in the
    attended keys."""
    eng = _ladder.engine(NAME, slots=2, kernels=True, config={
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    sched = eng.scheduler()
    sched.submit(_prompts((20, ))[0], max_new_tokens=8)
    sched.drain()
    total = eng.telemetry.counter_total
    # chunk 1 (16 columns, not final, alone: K = 1); chunk 2 (4 columns, final,
    # K = 4: 3 substeps); one decode sync (K = 4): its column and 3 substeps
    assert total("serving/ssd_chunk_tokens") == 4 * (16 + 4)
    assert total("serving/ssd_state_updates") == 4 * (3 + 1 + 3)
    # keys attended: the chunks' 16 and 20, then a key more each of 7 one-token forwards
    assert total("serving/attn_keys_live") == 4 * (16 + 20 + sum(range(21, 28)))
    assert total("serving/attn_keys_walked") >= total("serving/attn_keys_live")
    gauges = eng.telemetry.snapshot()["gauges"]
    assert gauges["serving/state_bytes_per_slot"] == sched.cache.state_bytes_per_slot() == 12800
    assert sched.cache.bytes_per_token() == 512
    eng.telemetry.close()


def test_the_block_has_both_mixers_leaves(tiny):
    model, params = tiny
    for i in range(4):
        assert set(params[f"layer_{i}"]) == {"attn_norm", "attn", "mamba2", "mlp_norm", "mlp"}
    assert set(params["layer_0"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert set(params["layer_0"]["mamba2"]) == {"in_proj", "conv", "conv_bias", "dt_bias", "A_log",
                                                "D", "norm", "out_proj"}
    assert set(params["layer_0"]["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    # no bias but the convolution's; the constants are no parameters
    assert all(set(v) == {"kernel"} for v in params["layer_0"]["attn"].values())
    assert set(params) == {"embed", "final_norm", "lm_head"} | {f"layer_{i}" for i in range(4)}
    abstract = jax.eval_shape(model.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == model.cfg.num_params()


def test_preset_builds_the_published_sizes():
    """33.64 B parameters in 72 two-mixer blocks of 430,120,032; the cut as
    the benchmark serves it: six whole layers and the whole vocabulary, 5.25
    B, 2,048 B a position a layer and 2,127,872 B of state and window a slot a
    layer, in the SAME layers."""
    from chipbench import cells
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    whole = get_model("falcon-h1-34b-instruct")
    cfg = whole.cfg
    assert cfg.layer_types == ("parallel_hybrid", ) * 72
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_heads, cfg.kv_heads, cfg.head_size,
            cfg.ffn_size, cfg.rope_theta, cfg.tie_embeddings) == (
        5120, 261120, 20, 4, 128, 21504, 1e11, False)
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups,
            cfg.ssm_conv_kernel, cfg.ssm_chunk_size, cfg.mamba2_inner,
            cfg.mamba2_conv_channels) == (32, 128, 256, 2, 4, 128, 4096, 5120)
    assert cfg.num_params() == 33_642_516_224
    abstract = jax.eval_shape(whole.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == cfg.num_params()
    layer = abstract["layer_0"]
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert (count(layer["attn"]), count(layer["mamba2"]), count(layer["mlp"]), count(layer)) == (
        31_457_280, 68_351_072, 330_301_440, 430_120_032)
    assert layer["mamba2"]["in_proj"]["kernel"].shape == (5120, 9248)
    config = cells.load_config("falcon-h1-34b-instruct")
    served = cells.build_model(config, dtype=jnp.bfloat16)
    pub = config["published"]
    assert served.cfg.layer_types == tuple(config["expect_lists"]["layer_types"])
    assert list(served.cfg.ssm_multipliers) == pub["ssm_multipliers"]
    assert [served.cfg.mlp_gate_multiplier, served.cfg.mlp_down_multiplier] == pub["mlp_multipliers"]
    for key in ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"):
        assert getattr(served.cfg, key) == pub[key], key
    assert served.cfg.num_params() == 5_254_594_112 == config["sizes"]["parameters_here"]
    pool = jax.eval_shape(lambda: served.init_cache(64, 4096))
    kv = SlotKVCache(pool, 64, 4096, kinds=served.cache_kinds())
    assert kv.bytes_per_token() == 12_288 == config["reference"]["kv_bytes_per_token"]
    assert kv.state_bytes_per_slot() == 12_767_232 == config["reference"]["state_bytes_per_slot"]
    assert kv.capacity_bytes() == 64 * (4096 * 12_288 + 12_767_232)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    assert shapes.count((64, 4, 4096, 128)) == 12 and shapes.count((64, 32, 128, 256)) == 6
    assert shapes.count((64, 1, 3, 5120)) == 6 and len(shapes) == 24
