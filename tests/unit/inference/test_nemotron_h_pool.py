"""A ``nemotron_h`` stack (``layer_types`` with ``mamba2``, ``moe``,
``attention``, ``mlp``: every block ONE sublayer; NVIDIA-Nemotron-3-Nano's
kinds) at a small size on the CPU in float32: the program against the plain
reference (``chipbench/references/nemotron_h.py``: one causal forward, no
cache, the recurrence token by token, a loop over experts), the chunked
matrix form against the recurrence, the router's rule, the share of the
experts against the uncut layer, the slot pool's span programs over Mamba-2
state beside K/V rows, the refusals, the parameter trees and the sizes of the
published preset.

Weights: the benchmark's own draw (``serve_nemotron_h.nemotron_params``: the
layers' published starts) with biases and norm scales moved off 0 and 1, so
that a dropped bias or scale shows. ``TOL``: the reference's float32 limit,
1e-5; the served path reads 1e-6 at worst; a wrong state, span, weight or
choice gives 1e-3 and up."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import nemotron_h as ref
from deepspeed_tpu.models import get_model, mamba2, nemotron_h_layers
from deepspeed_tpu.moe.sharded_moe import sigmoid_serving_choice

from . import _ladder
from ._serving import VOCAB
from ._serving import prompts as _prompts

NAME = "tiny-nemotron-h"
_, HP, TOL = _ladder.reference(NAME)


@pytest.fixture(scope="module")
def tiny():
    return _ladder.built(NAME)


def _tree(model, params):
    return _ladder.tree_of(NAME, model, params)


def _agrees(model, params, ids, hp=HP):
    res = _ladder.agrees(NAME, model, params, ids, hp)
    assert res["ok"], res["error"]


class TestLadder(_ladder.Ladder):
    twin = NAME

    def served_pool(self, case, sched):
        assert sched.moe_dispatch_programs["dense"] == 0 < sched.moe_dispatch_programs["sparse"]

    def more_refusals(self, eng, sched):
        """Experts outside ``moe`` layers, a mix with two-sublayer kinds, the
        training router, an int8 tier."""
        model, _ = _ladder.built(NAME)
        with pytest.raises(NotImplementedError, match="no int8 tier"):
            model.init_cache(2, 64, quantized=True)
        cfg = model.cfg
        with pytest.raises(ValueError, match="do not mix"):
            dataclasses.replace(cfg, num_layers=2, layer_types=("mamba2", "full_attention"))
        with pytest.raises(ValueError, match="moe layers need num_experts"):
            dataclasses.replace(cfg, num_layers=1, layer_types=("mamba2", ))
        with pytest.raises(ValueError, match="experts in a mixer-and-FFN block"):
            dataclasses.replace(get_model("tiny-sambay").cfg, num_experts=4, moe_dropless=True)
        with pytest.raises(ValueError, match="ssm_num_heads"):
            dataclasses.replace(cfg, ssm_groups=3)
        with pytest.raises(ValueError, match="no capacity-buffered path"):
            dataclasses.replace(get_model("tiny-moe").cfg, moe_scoring="sigmoid")


@pytest.mark.parametrize("kinds", [("mamba2", ), ("attention", ), ("moe", ), ("mlp", )])
def test_each_sublayer_alone_matches_its_reference(tiny, kinds):
    """A stack of the one kind, 50 positions: six chunks of 8 and a partial
    one, so the state is carried and the padding past the end is inert."""
    cfg = dataclasses.replace(tiny[0].cfg, num_layers=1, layer_types=kinds,
                              num_experts=8 if kinds == ("moe", ) else 0)
    model = type(tiny[0])(cfg)
    _agrees(model, _ladder.params_of(NAME, model, seed=11),
            jax.random.randint(jax.random.key(2), (2, 50), 0, VOCAB))


@pytest.mark.parametrize("T, chunk", [(1, 8), (5, 8), (8, 8), (29, 8), (64, 16), (50, 128)])
def test_chunked_matrix_form_is_the_recurrence(T, chunk):
    """``ssd_chunked`` from a non-zero state against a scan of ``ssd_step``,
    at lengths under, at and over a chunk and not a multiple of it; a column
    with Delta 0 (padding, a dead column) leaves the state as it is."""
    B, nh, hd, N, G = 2, 4, 8, 16, 2
    ks = jax.random.split(jax.random.key(T), 8)
    x = jax.random.normal(ks[0], (B, T, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, nh)) - 2.0)
    dt = dt.at[1, T // 2:].set(0.0)  # row 1 lives over its first half only
    a = -jnp.exp(mamba2.mamba2_a_log_init(ks[2], (nh, )))
    Bm, Cm = (jax.random.normal(k, (B, T, G, N)) for k in ks[3:5])
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (nh, ))
    S0 = jax.random.normal(ks[6], (B, nh, hd, N))
    per_head = lambda m: jnp.repeat(m, nh // G, axis=1)
    S, want = S0, []
    for t in range(T):
        y, S = mamba2.ssd_step(S, x[:, t], dt[:, t], a, per_head(Bm[:, t]), per_head(Cm[:, t]), D)
        want.append(y)
    got, S_got = mamba2.ssd_chunked(S0, x, dt, a, Bm, Cm, D, chunk)
    np.testing.assert_allclose(got, jnp.stack(want, axis=1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S_got, S, rtol=1e-5, atol=1e-5)
    half, S_half = mamba2.ssd_chunked(S0[1:], x[1:, :T // 2], dt[1:, :T // 2], a, Bm[1:, :T // 2],
                                      Cm[1:, :T // 2], D, chunk) if T > 1 else (None, S0[1:])
    np.testing.assert_allclose(S_got[1:], S_half, rtol=1e-5, atol=1e-5)


def test_router_chooses_by_s_plus_b_and_weighs_by_s():
    """Choice by ``s + b``, weights by ``s`` without ``b``, renormalised over
    the k (the program then multiplies by 2.5); ties go to the lowest id."""
    logits = jnp.log(jnp.asarray([[0.6, 0.5, 0.4, 0.3], [0.2, 0.2, 0.2, 0.2],
                                  [0.9, 0.1, 0.8, 0.7]]) / (1 - jnp.asarray(
                                      [[0.6, 0.5, 0.4, 0.3], [0.2, 0.2, 0.2, 0.2],
                                       [0.9, 0.1, 0.8, 0.7]])))  # sigmoid^-1
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.35])  # lifts expert 3 past 0 and 1 in row 0
    ids, w = sigmoid_serving_choice(logits, bias, 2)
    assert ids.tolist() == [[3, 0], [3, 0], [3, 0]]  # row 1: 3 by its bias, then the lowest id
    np.testing.assert_allclose(w[0], [0.3 / 0.9, 0.6 / 0.9], rtol=1e-6)  # by s, not s + b
    np.testing.assert_allclose(w[2], [0.7 / 1.6, 0.9 / 1.6], rtol=1e-6)
    ids0, w0 = sigmoid_serving_choice(logits, jnp.zeros(4), 2)
    assert ids0.tolist() == [[0, 1], [0, 1], [0, 2]]
    np.testing.assert_allclose(jnp.sum(w0, axis=-1), 1.0, rtol=1e-6)
    # the reference's rule is the same rule, scale included
    lp = {"gate": jnp.eye(4), "bias": bias}
    hp = dict(HP, top_k=2)
    u = logits[None]
    rw, _ = ref.route(u, lp, hp)
    np.testing.assert_allclose(rw[0, 0], [2.5 * 0.6 / 0.9, 0, 0, 2.5 * 0.3 / 0.9], rtol=1e-6)


def test_expert_layer_scales_by_the_routed_factor(tiny):
    """The program's expert layer against the reference's: the 2.5 is applied
    to the routed part and not to the shared expert."""
    model, params = tiny
    cfg = dataclasses.replace(model.cfg, num_layers=1, layer_types=("moe", ))
    one = type(model)(cfg)
    p = _ladder.params_of(NAME, one, seed=5)
    ids = jax.random.randint(jax.random.key(3), (1, 20), 0, VOCAB)
    _agrees(one, p, ids)
    with pytest.raises(AssertionError):
        _agrees(one, p, ids, dict(HP, routed_scale=1.0))


def test_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """Four shares of 2 of the 8 experts, each computed by the PROGRAM's
    expert layer as one chip of four would (router over all 8, top-2,
    selection bias, its own two experts' part), with the shared expert, which
    every chip computes alike, counted once: their sum is what the uncut
    reference gives for the whole layer."""
    from deepspeed_tpu.moe.layer import MoE
    model, _ = tiny
    whole_cfg = dataclasses.replace(model.cfg, num_layers=1, layer_types=("moe", ))
    p = _ladder.params_of(NAME, type(model)(whole_cfg), seed=9)["layer_0"]["moe"]
    x = jax.random.normal(jax.random.key(4), (2, 13, 64))
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in dict(
        gate=p["gate"], bias=p["e_score_correction_bias"], w_up=p["experts"]["up_proj"],
        w_down=p["experts"]["down_proj"], s_up=p["shared_expert"]["up_proj"]["kernel"],
        s_down=p["shared_expert"]["down_proj"]["kernel"]).items()}
    with jax.default_matmul_precision("highest"):
        routed, _ = ref.routed(x, lp, HP, first=0, held=8)
        shared = ref.shared(x, lp)
        total = jnp.zeros_like(x)
        for first in (0, 2, 4, 6):
            cfg = dataclasses.replace(whole_cfg, moe_experts_held=2, moe_first_expert=first)
            share = dict(p, experts={k: v[first:first + 2] for k, v in p["experts"].items()})
            part = MoE(cfg).apply({"params": share}, x, serving=True)
            # one share against the reference given the same share
            mine, _ = ref.routed(x, dict(lp, w_up=lp["w_up"][first:first + 2],
                                         w_down=lp["w_down"][first:first + 2]), HP, first=first)
            np.testing.assert_allclose(part - shared, mine, atol=1e-5)
            total = total + (part - shared)
    np.testing.assert_allclose(total + shared, routed + shared, atol=1e-5)
    assert float(jnp.max(jnp.abs(routed))) > 10 * 1e-5


@pytest.mark.parametrize("first, held", [(0, 8), (2, 4)], ids=["all_held", "a_share"])
def test_both_dispatches_of_the_expert_layer_agree_bit_for_bit(tiny, monkeypatch, first, held):
    """The program's expert layer (sigmoid router with its selection bias,
    relu2 experts of two matrices, the 2.5, a shared expert) by the sparse
    dispatch and by the dense product over the experts held, the rule
    steered either way: the same bits, for all 8 experts and for experts 2-5
    of 8, a row past its span adding nothing to either."""
    from deepspeed_tpu.moe import layer as moe_layer
    model, _ = tiny
    cfg = dataclasses.replace(model.cfg, num_layers=1, layer_types=("moe", ),
                              moe_experts_held=held, moe_first_expert=first)
    p = _ladder.params_of(NAME, type(model)(dataclasses.replace(cfg, moe_experts_held=8, moe_first_expert=0)),
                seed=9)["layer_0"]["moe"]
    p = dict(p, experts={k: v[first:first + held] for k, v in p["experts"].items()})
    x = jax.random.normal(jax.random.key(4), (3, 11, 64))
    spans = jnp.asarray([11, 4, 0], jnp.int32)
    got = {}
    for name, pays in (("sparse", False), ("dense", True)):
        monkeypatch.setattr(moe_layer, "dense_held_pays", lambda *shape: pays)
        before = moe_layer.traced_dispatches()
        got[name] = jax.jit(lambda x: moe_layer.MoE(cfg).apply(
            {"params": p}, x, serving=True, q_spans=spans))(x)
        after = moe_layer.traced_dispatches()
        assert (after[0] - before[0], after[1] - before[1]) == ((0, 1) if pays else (1, 0))
    assert np.array_equal(got["sparse"], got["dense"])
    assert float(jnp.abs(got["dense"][0]).max()) > 1e-3


@pytest.mark.parametrize("slots, chunk, rows", [(4, 16, "chunk"), (12, 64, "both"), (2, 4, "neither")])
def test_the_rule_picks_each_step_programs_dispatch(tiny, monkeypatch, slots, chunk, rows):
    """8 experts, top-2: a forward's dispatch follows what the rule
    (``dense_held_pays``, steered here to rows an expert alone) says of its
    own shape, the column of ``slots`` decoding rows and the chunk's forward
    each for itself, and a program counts as ``dense`` if any layer of it
    went dense. The logits are the reference's either way."""
    from deepspeed_tpu.moe import layer as moe_layer
    model, params = tiny
    cfg = model.cfg
    # the tiny widths are no multiples of 256 and would go dense at every N: by rows an expert
    monkeypatch.setattr(moe_layer, "dense_held_pays", lambda N, k, E, H, F: 2 * E < N * k)
    pays = lambda n: 2 * cfg.num_experts < n * cfg.moe_top_k
    sched = _ladder.engine(NAME, slots, chunk, 4, fresh=True).scheduler()
    # under the live-rows split the chunk's forward is (1, chunk)
    split = sched._splits_chunk(("fused", False, True, chunk, 4))
    assert (pays(slots), pays(chunk if split else slots * chunk)) == {
        "chunk": (False, True), "both": (True, True), "neither": (False, False)}[rows]
    prompts = _prompts((21, 9))
    handles = [sched.submit(q, max_new_tokens=6, collect_logits=True) for q in prompts]
    sched.drain()
    tree = _tree(model, params)
    for q, h in zip(prompts, handles):
        ids = jnp.asarray([q + [int(t) for t in h.result()[:-1]]], jnp.int32)
        want, routing = ref.forward(tree, ids, HP, first=len(q) - 1,
                                    choice=h.result_choice()[:, None, :ids.shape[1]])
        assert ref.compare(h.result_logits(), want[0], routing["followed"], routing["refused"],
                           tol=TOL)["ok"]
    programs = sched.moe_dispatch_programs
    # ``sparse``: every program of one device; ``dense_held``: those with a dense layer
    assert programs["dense"] == 0 < programs["sparse"]
    assert (programs["dense_held"] > 0) is (rows != "neither"), programs
    assert (programs["dense_held"] < programs["sparse"]) is (rows != "both"), programs


def test_neighbours_in_other_slots_change_nothing(tiny):
    """One request alone in the pool and the same request among three others
    (another routing mix in every expert layer, other states beside its own,
    another slot, its chunks riding other programs): the same logits to the
    reference's float32 limit (the CPU's products round with the block's
    shape, by 3e-7)."""
    prompt = _prompts((45, ), seed=8)[0]
    alone = _ladder.engine(NAME, slots=4, chunk=16).scheduler()
    want = alone.submit(prompt, max_new_tokens=12, collect_logits=True)
    alone.drain()
    crowd = _ladder.engine(NAME, slots=4, chunk=16).scheduler()
    others = [crowd.submit(p, max_new_tokens=30) for p in _prompts((21, 60), seed=9)]
    got = crowd.submit(prompt, max_new_tokens=12, collect_logits=True)
    late = crowd.submit(_prompts((35, ), seed=10)[0], max_new_tokens=20)
    crowd.drain()
    assert all(h.done for h in others + [late]) and got._req.slot != want._req.slot
    res = ref.compare(got.result_logits(), want.result_logits(), tol=TOL)
    assert res["ok"] and res["rows"] == 12, res["error"]


def test_counters_of_required_state_work(tiny, tmp_path):
    """Hand-counted: one request of 20 prompt tokens, chunk 16, K = 4, alone
    in the pool; 3 Mamba-2 layers, 3 expert layers of 8 experts top-2."""
    eng = _ladder.engine(NAME, slots=2, config={
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    sched = eng.scheduler()
    sched.submit(_prompts((20, ))[0], max_new_tokens=8)
    sched.drain()
    total = eng.telemetry.counter_total
    # chunk 1 (16 columns, not final: it stands still in no substep, alone:
    # K = 1); chunk 2 (4 columns, final, K = 4: 3 substeps); one decode sync
    # (K = 4): its column and 3 substeps
    assert total("serving/ssd_chunk_tokens") == 3 * (16 + 4)
    assert total("serving/ssd_state_updates") == 3 * (3 + 1 + 3)
    # every live position routes 2 pairs in each of 3 layers, all held here
    positions = 16 + 4 + 3 + 4
    assert total("serving/moe_pairs_here") == 3 * 2 * positions
    assert not total("serving/moe_pairs_elsewhere")
    assert total("serving/moe_layer_calls") == 3 * (1 + 4 + 4)
    gauges = eng.telemetry.snapshot()["gauges"]
    assert gauges["serving/state_bytes_per_slot"] == sched.cache.state_bytes_per_slot() == 9600
    assert sched.cache.bytes_per_token() == 256
    eng.telemetry.close()


def test_a_one_sublayer_block_has_no_leaves_of_the_absent_half(tiny):
    model, params = tiny
    want = {"mamba2": {"norm", "mamba2"}, "moe": {"norm", "moe"}, "attention": {"norm", "attn"},
            "mlp": {"norm", "mlp"}}
    for i, kind in enumerate(model.cfg.layer_types):
        assert set(params[f"layer_{i}"]) == want[kind], (i, kind)
    assert set(params["layer_1"]["moe"]["experts"]) == {"up_proj", "down_proj"}  # no gate leaf
    assert set(params["layer_1"]["moe"]["shared_expert"]) == {"up_proj", "down_proj"}
    assert params["layer_1"]["moe"]["shared_expert"]["up_proj"]["kernel"].shape == (64, 64)
    assert params["layer_1"]["moe"]["e_score_correction_bias"].shape == (8, )
    assert set(params["layer_0"]["mamba2"]) == {"in_proj", "conv", "conv_bias", "dt_bias", "A_log",
                                                "D", "norm", "out_proj"}
    abstract = jax.eval_shape(model.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == model.cfg.num_params()
    # the pool: state for a Mamba-2 layer, rows for attention, nothing for an FFN
    kinds = model.cache_kinds()
    assert [k for k in kinds[0]] == ["state", None, "state", "rows", None, None, "state", None]


def test_preset_builds_the_published_sizes():
    """31.58 B parameters in 52 one-sublayer blocks (23 : 23 : 6); the chip's
    share as the benchmark cuts it: 5.28 B, 1,085,440 B of state and window a
    slot a Mamba-2 layer, 1,024 B a position an attention layer, nothing for
    an expert layer."""
    from chipbench import cells
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    whole = get_model("nemotron-3-nano-30b-a3b")
    cfg = whole.cfg
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert cfg.layer_types == nemotron_h_layers(pattern) and len(pattern) == 52
    assert [cfg.layer_types.count(k) for k in ("mamba2", "moe", "attention", "mlp")] == [
        23, 23, 6, 0]
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_heads, cfg.kv_heads, cfg.head_size) == (
        2688, 131072, 32, 2, 128)
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups,
            cfg.ssm_conv_kernel, cfg.ssm_chunk_size, cfg.mamba2_inner,
            cfg.mamba2_conv_channels) == (64, 64, 128, 8, 4, 128, 4096, 6144)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.expert_ffn_size, cfg.shared_ffn_size,
            cfg.moe_routed_scale, cfg.moe_scoring, cfg.activation) == (
        128, 6, 1856, 3712, 2.5, "sigmoid", "relu2")
    assert cfg.num_params() == 31_577_940_288 and round(cfg.num_params() / 1e9, 2) == 31.58
    abstract = jax.eval_shape(whole.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == cfg.num_params()
    in_proj = abstract["layer_0"]["mamba2"]["in_proj"]["kernel"]
    assert in_proj.shape == (2688, 10304)
    config = cells.load_config("nemotron-3-nano-30b-a3b")
    served = cells.build_model(config, dtype=jnp.bfloat16)
    assert served.cfg.layer_types == nemotron_h_layers(pattern[:16])
    assert served.cfg.num_params() == 5_282_534_208 == config["sizes"]["parameters_here"]
    pool = jax.eval_shape(lambda: served.init_cache(192, 4096))
    kv = SlotKVCache(pool, 192, 4096, kinds=served.cache_kinds())
    assert kv.bytes_per_token() == 2 * 1024 == config["reference"]["kv_bytes_per_token"]
    assert kv.state_bytes_per_slot() == 7 * 1_085_440 == config["reference"][
        "state_bytes_per_slot"]
    assert kv.capacity_bytes() == 192 * (4096 * 2048 + 7 * 1_085_440)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    assert shapes.count((192, 64, 64, 128)) == 7 and shapes.count((192, 1, 3, 6144)) == 7
    assert shapes.count((192, 2, 4096, 128)) == 4 and len(shapes) == 18
