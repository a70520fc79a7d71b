"""An ``lfm2_moe`` stack (``layer_types`` with ``short_conv`` beside
``full_attention``, two leading dense layers, gated experts under a sigmoid
router with NO shared expert, every expert held; LFM2-8B-A1B's kinds) at a
small size on the CPU in float32: the program against the plain reference
(``chipbench/references/lfm2_moe.py``: one causal forward, no cache, a loop
over experts), the mixer's chunk form, one-token form and a plain loop
against each other, the router's rule with the published epsilon, the slot
pool's span programs over two carried rows a slot beside packed K/V rows, the
refusals, the parameter tree and the sizes of the published preset.

Weights: the benchmark's own draw (``serve_nemotron_h.nemotron_params``: taps
uniform in (-1, 1), last matrices centred) with norm scales moved off 1, so
that a dropped scale shows. ``TOL``: the reference's float32 limit, 1e-5; the
served path reads 2e-6 at worst; a wrong carried row, span, weight or choice
gives 1e-3 and up."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import available_models, get_model, lfm2_layers
from deepspeed_tpu.models.transformer import ShortConv
from deepspeed_tpu.moe.sharded_moe import sigmoid_serving_choice

from . import _ladder
from ._serving import prompts as _prompts

NAME = "tiny-lfm2-moe"
ref, HP, TOL = _ladder.reference(NAME)


@pytest.fixture(scope="module")
def tiny():
    return _ladder.built(NAME)


class TestLadder(_ladder.Ladder):
    """(The served prompts: one inside a chunk (9), one over three chunks with
    a partial last (37 = 16 + 16 + 5), last chunks of ONE and of TWO live
    positions (33, 34: shorter than and equal to the two carried rows), and
    every chunk of 2 or of 1; the paged kernels over the packed K/V leaf.)"""
    twin = NAME

    def served_request(self, case, prompt, handle, tree, ids, kw):
        # a program that lost its rows at a call boundary would not pass
        lost, _ = ref.forward(tree, ids, HP, **kw, call_starts=ref.serving_calls(
            len(prompt), ids.shape[1], case["chunk"]))
        assert not ref.compare(handle.result_logits(), lost[0], tol=TOL)["ok"]

    def served_pool(self, case, sched):
        programs = sched.moe_dispatch_programs
        assert programs["dense"] == 0 < programs["sparse"]

    def used_pool(self, used):
        """The fillers left carried rows in slot 0 of every convolution layer."""
        rows = [leaf for leaf, k in zip(jax.tree_util.tree_leaves(used.cache.pool),
                                        used.cache.leaf_kinds) if k == "state"]
        assert len(rows) == 5 and all(bool(jnp.any(leaf[0] != 0)) for leaf in rows)

    def more_refusals(self, eng, sched):
        """An int8 tier, taps under 2, a mix with the SambaY and one-sublayer
        kinds, experts without the dropless dispatch, a drafting module, the
        packed geometry beside any other state."""
        model = eng.module
        cfg = model.cfg
        with pytest.raises(NotImplementedError, match="no int8 tier"):
            model.init_cache(2, 64, quantized=True)
        with pytest.raises(ValueError, match="short_conv_kernel"):
            dataclasses.replace(cfg, short_conv_kernel=1)
        with pytest.raises(ValueError, match="do not mix"):
            dataclasses.replace(cfg, layer_types=("short_conv", "mamba") + cfg.layer_types[2:])
        with pytest.raises(ValueError, match="do not mix"):
            dataclasses.replace(cfg, layer_types=("short_conv", "mlp") + cfg.layer_types[2:])
        # (experts beside a linear-attention layer are served since PR 54: the mix builds)
        mixed = dataclasses.replace(cfg, layer_types=("short_conv", "linear_attention")
                                    + cfg.layer_types[2:], linear_num_heads=4,
                                    linear_key_head_dim=8, linear_value_head_dim=16)
        assert mixed.layer_parts(2) == ("full_attention", "moe")
        with pytest.raises(ValueError, match="linear_attention and short_conv layers"):
            dataclasses.replace(mixed, moe_dropless=False)
        with pytest.raises(ValueError, match="short_conv or one-sublayer"):
            dataclasses.replace(cfg, mtp_layers=1)
        with pytest.raises(ValueError, match="only a diff_attention or full_attention layer"):
            dataclasses.replace(cfg, layer_windows=(8, 0, 0, 0, 0, 0))
        # packed K/V rows rest beside a short convolution's rows and beside no other state
        hybrid = get_model("tiny-hybrid")
        with pytest.raises(NotImplementedError, match="no packed geometry"):
            type(hybrid)(dataclasses.replace(hybrid.cfg, head_dim=64)).init_cache(2, 64)


def _mixer(H=32, W=3, seed=0):
    cfg = dataclasses.replace(get_model("tiny-lfm2-moe", dtype=jnp.float32).cfg, hidden_size=H,
                              short_conv_kernel=W)
    mixer = ShortConv(cfg)
    x = jax.random.normal(jax.random.key(seed), (1, 12, H))
    params = mixer.init(jax.random.key(seed + 1), x, None, None)["params"]
    return mixer, params


@pytest.mark.parametrize("W", [3, 4])
def test_mixer_chunk_form_one_token_form_and_a_plain_loop_agree(W):
    """Twelve positions of one row: the forward without a cache, a plain loop
    over positions, and the span programs' calls over a slot (a chunk of 5
    live columns in a block of 8, a chunk of 1 in a block of 8, a chunk of 2,
    then a column a call) give the same outputs; the rows left behind are
    ``z`` of the last ``W - 1`` LIVE positions after every call; an idle
    neighbour's rows come out bit for bit; a fresh row starts from zeros
    whatever the slot held."""
    H = 32
    mixer, params = _mixer(H, W)
    x = jax.random.normal(jax.random.key(5), (1, 12, H))
    apply = lambda x, *a: mixer.apply({"params": params}, x, None, None, None, *a)
    whole, none = apply(x)
    assert none is None
    bcx = x @ params["in_proj"]["kernel"]
    z = bcx[..., :H] * bcx[..., 2 * H:]
    loop = jnp.stack([sum(params["conv"][:, k] * z[:, t - (W - 1) + k] for k in range(W)
                          if t - (W - 1) + k >= 0) for t in range(12)], axis=1)
    np.testing.assert_allclose(whole, (bcx[..., H:2 * H] * loop) @ params["out_proj"]["kernel"],
                               atol=1e-5)
    # slot 0 serves the row; slot 1 idles with rows of its own
    junk = jax.random.normal(jax.random.key(6), (2, 1, W - 1, H))
    pool, pos, outs = (junk, ), 0, []
    for take, width in ((5, 8), (1, 8), (2, 2), (1, 1), (1, 1), (1, 1), (1, 1)):
        block = jnp.zeros((2, width, H)).at[0, :take].set(x[0, pos:pos + take])
        block = block.at[0, take:].set(9.0).at[1].set(3.0)  # padding columns, an idle row
        out, pool = apply(block, pool, None, None, jnp.asarray([pos, 7]),
                          jnp.asarray([take, 0]))
        outs.append(out[0, :take])
        pos += take
        want_rows = jnp.pad(z[0, :pos], ((W - 1, 0), (0, 0)))[-(W - 1):]
        np.testing.assert_allclose(pool[0][0, 0], want_rows, atol=1e-6)
        np.testing.assert_array_equal(pool[0][1], junk[1])
    assert pos == 12
    np.testing.assert_allclose(jnp.concatenate(outs), whole[0], atol=1e-5)
    # a wider cache tree (split K/V beside it): the other places hold nothing
    _, wider = apply(x[:, :1], (junk[:1], None), None, None, jnp.asarray([3]), jnp.asarray([1]))
    assert len(wider) == 2 and wider[1] is None
    with pytest.raises(NotImplementedError, match="span programs"):
        apply(x, (junk[:1], ), 0)
    with pytest.raises(NotImplementedError, match="short_conv layer serves without adapters"):
        mixer.apply({"params": params}, x, None, None, jnp.ones((1, 12), bool))


def test_what_each_layer_kind_declares_and_what_the_pool_counts(tiny):
    """A short_conv layer declares ONE ``state`` leaf ``(slots, 1, L - 1,
    hidden)`` and nothing else, an attention layer its packed rows; the pool
    counts their bytes apart."""
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    model, _ = tiny
    spec = model.cache_spec(4, 64)
    conv = (("state", (4, 1, 2, 256)), )
    assert [tuple((k, s) for k, s, *_ in layer) for layer in spec] == [
        conv, conv, (("rows", (4, 2, 64, 128)), ), conv, conv, conv]
    assert model.cache_kinds() == (("state", "state", "rows", "state", "state", "state"), )
    pool = model.init_cache(4, 64)
    kv = SlotKVCache(pool, 4, 64, page_size=64, kinds=model.cache_kinds())
    assert kv.bytes_per_token() == 2 * 128 * 4 and kv.state_bytes_per_slot() == 5 * 2 * 256 * 4
    assert kv.window_bytes_per_slot() == 0
    assert kv.capacity_bytes() == 4 * (64 * 1024 + 10240)
    # head size 16: K and V rest split, the convolution's leaf beside them
    split = type(model)(dataclasses.replace(model.cfg, head_dim=16))
    assert split.cache_kinds() == (("state", "state", "rows", "state", "state", "state"),
                                   (None, None, "rows", None, None, None))


def test_router_with_the_published_epsilon_and_the_older_ones_as_they_were():
    """Choice by ``s + b``, weights by ``s`` without ``b`` over ``sum + eps``;
    ties go to the lowest id. The default is the three older sigmoid
    configurations' 1e-20, bit for bit what the expression gave before it
    became an argument; ``lfm2_moe`` takes its 1e-6 from the configuration."""
    s = jnp.asarray([[0.6, 0.5, 0.4, 0.3], [0.2, 0.2, 0.2, 0.2], [0.9, 0.1, 0.8, 0.7]])
    logits = jnp.log(s / (1 - s))
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.35])
    ids, w = sigmoid_serving_choice(logits, bias, 2, 1e-6)
    assert ids.tolist() == [[3, 0], [3, 0], [3, 0]]  # row 1: 3 by its bias, then the lowest id
    np.testing.assert_allclose(w[0], [0.3 / (0.9 + 1e-6), 0.6 / (0.9 + 1e-6)], rtol=1e-6)
    assert float(jnp.sum(w[0])) < 1.0 - 5e-7
    old_ids, old_w = sigmoid_serving_choice(logits, bias, 2)
    sg = jax.nn.sigmoid(logits.astype(jnp.float32))
    picked = jnp.take_along_axis(sg, old_ids, axis=-1)
    as_it_was = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    assert np.array_equal(old_ids, ids) and np.array_equal(old_w, as_it_was)
    assert np.array_equal(old_w, sigmoid_serving_choice(logits, bias, 2, 1e-20)[1])
    assert not np.array_equal(old_w, w)
    older = [n for n in available_models()
             if "ling" not in n  # (refused whole; its router is PR 54's tests')
             and get_model(n).cfg.moe_scoring == "sigmoid" and "lfm2" not in n]
    assert {"nemotron-3-nano-30b-a3b", "k-exaone-236b-a23b"} <= set(older)
    assert all(get_model(n).cfg.moe_renorm_eps == 1e-20 for n in older)
    assert {get_model(n).cfg.moe_renorm_eps for n in ("lfm2-8b-a1b", "tiny-lfm2-moe")} == {1e-6}


@pytest.mark.parametrize("dispatch", ["sparse", "dense"])
def test_whole_expert_layer_without_a_shared_expert(tiny, monkeypatch, dispatch):
    """The program's expert layer with every expert held and no shared expert
    (sigmoid router, selection bias, the 1e-6, scale 1) by either dispatch
    against the reference's loop over experts; a row past its span adds
    nothing; the tree has no shared leaf."""
    from deepspeed_tpu.moe import layer as moe_layer
    model, params = tiny
    p = params["layer_3"]["moe"]
    assert set(p) == {"gate", "e_score_correction_bias", "experts"}
    assert model.cfg.experts_held == model.cfg.num_experts == 8
    x = jax.random.normal(jax.random.key(4), (2, 11, 256))
    monkeypatch.setattr(moe_layer, "dense_held_pays", lambda *shape: dispatch == "dense")
    with jax.default_matmul_precision("highest"):
        got = moe_layer.MoE(model.cfg).apply({"params": p}, x, serving=True,
                                             q_spans=jnp.asarray([11, 4]))
        lp = {k: jnp.asarray(v, jnp.float32) for k, v in dict(
            gate=p["gate"], bias=p["e_score_correction_bias"], w_gate=p["experts"]["gate_proj"],
            w_up=p["experts"]["up_proj"], w_down=p["experts"]["down_proj"]).items()}
        want, _ = ref.routed(x, lp, HP)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_allclose(got[1, :4], want[1, :4], atol=1e-5)
    assert not np.any(np.asarray(got[1, 4:])) and float(jnp.abs(want).max()) > 1e-3


@pytest.mark.parametrize("eps", [1e-20, 1e-6, 0.5])
def test_expert_layer_takes_its_epsilon_from_the_configuration(tiny, eps):
    """``moe_renorm_eps`` reaches the layer's weights: the layer built at each
    value equals the reference at that value (0.5 is no model's: it makes the
    field visible in float32, where 1e-6 on a sum near 1 moves a weight by one
    part in a million), and only there."""
    from deepspeed_tpu.moe import layer as moe_layer
    model, params = tiny
    p = params["layer_3"]["moe"]
    x = jax.random.normal(jax.random.key(6), (1, 7, 256))
    cfg = dataclasses.replace(model.cfg, moe_renorm_eps=eps)
    with jax.default_matmul_precision("highest"):
        got = moe_layer.MoE(cfg).apply({"params": p}, x, serving=True)
        lp = {k: jnp.asarray(v, jnp.float32) for k, v in dict(
            gate=p["gate"], bias=p["e_score_correction_bias"], w_gate=p["experts"]["gate_proj"],
            w_up=p["experts"]["up_proj"], w_down=p["experts"]["down_proj"]).items()}
        want, _ = ref.routed(x, lp, dict(HP, renorm_eps=eps))
        other, _ = ref.routed(x, lp, dict(HP, renorm_eps=0.25))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(got - other).max()) > 1e-3


def test_counters_of_required_convolution_work(tiny, tmp_path):
    """Hand-counted: one request of 20 prompt tokens, chunk 16, K = 4, alone
    in the pool; 5 short-convolution layers, 4 expert layers of 8 experts
    top-2, all held."""
    eng = _ladder.engine(NAME, slots=2, config={
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    sched = eng.scheduler()
    sched.submit(_prompts((20, ))[0], max_new_tokens=8)
    sched.drain()
    total = eng.telemetry.counter_total
    # chunk 1 (16 columns, not final, alone: K = 1); chunk 2 (4 columns, final,
    # K = 4: 3 substeps); one decode sync (K = 4): its column and 3 substeps.
    # No program of 2 slots splits its first forward: 1 + 4 + 4 forwards
    assert total("serving/short_conv_chunk_tokens") == 5 * (16 + 4)
    assert total("serving/short_conv_updates") == 5 * (3 + 1 + 3)
    assert total("serving/short_conv_layer_calls") == 5 * (1 + 4 + 4)
    assert not total("serving/ssd_state_updates")
    positions = 16 + 4 + 3 + 4
    assert total("serving/moe_pairs_here") == 4 * 2 * positions
    assert not total("serving/moe_pairs_elsewhere")  # the whole router is here
    assert total("serving/moe_layer_calls") == 4 * (1 + 4 + 4)
    gauges = eng.telemetry.snapshot()["gauges"]
    assert gauges["serving/state_bytes_per_slot"] == sched.cache.state_bytes_per_slot() == 10240
    assert gauges["serving/kv_pool_packed"] == 1
    eng.telemetry.close()


def test_a_split_chunk_sync_counts_two_first_forwards():
    """The observer alone: a program whose first forward runs as two over the
    live rows calls every layer twice for it."""
    import types
    from deepspeed_tpu.inference.required_work import RequiredWork
    counted = {}
    tel = types.SimpleNamespace(enabled=True, counter=lambda name, n=1: counted.__setitem__(
        name, counted.get(name, 0) + n))
    model = get_model("tiny-lfm2-moe")
    work = RequiredWork(tel, model, types.SimpleNamespace(num_slots=8, max_len=64), 1,
                        state_pool=True)
    assert (work.conv_layers, work.ssd_layers) == (5, 0)
    spans = np.asarray([1, 1, 0, 40, 1, 0, 0, 1], np.int32)
    work._count_state_updates(spans, 4, (3, False), 2)
    assert counted == {"serving/short_conv_updates": 5 * (4 + 4 * 3),
                       "serving/short_conv_chunk_tokens": 5 * 40,
                       "serving/short_conv_layer_calls": 5 * (2 + 3)}


def test_the_block_has_the_leaves_of_its_two_halves(tiny):
    model, params = tiny
    for i, kind in enumerate(model.cfg.layer_types):
        mixer = "conv" if kind == "short_conv" else "attn"
        ffn = "mlp" if i < 2 else "moe"
        assert set(params[f"layer_{i}"]) == {"attn_norm", mixer, "mlp_norm", ffn}, (i, kind)
    assert set(params["layer_0"]["conv"]) == {"in_proj", "conv", "out_proj"}
    assert params["layer_0"]["conv"]["in_proj"]["kernel"].shape == (256, 768)
    assert params["layer_0"]["conv"]["conv"].shape == (256, 3)
    assert set(params["layer_2"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                                              "k_norm"}
    assert params["layer_2"]["attn"]["q_norm"]["scale"].shape == (64, )
    assert "lm_head" not in params  # tied
    abstract = jax.eval_shape(model.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == model.cfg.num_params()


def test_preset_builds_the_published_sizes():
    """8.34 B parameters in 24 blocks (18 : 6), 22 of them expert layers; the
    first pipeline stage as the benchmark cuts it: 3.93 B, 8,192 B of carried
    rows a slot a convolution layer, 2,048 B a position an attention layer."""
    from chipbench import cells
    from deepspeed_tpu.inference.kv_cache import SlotKVCache
    whole = get_model("lfm2-8b-a1b")
    cfg = whole.cfg
    assert cfg.layer_types == lfm2_layers("ccac" * 5 + "cacc") and cfg.num_layers == 24
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_heads, cfg.kv_heads, cfg.head_size,
            cfg.short_conv_kernel, cfg.ffn_size) == (2048, 65536, 32, 8, 64, 3, 7168)
    assert (cfg.num_experts, cfg.experts_held, cfg.moe_top_k, cfg.expert_ffn_size,
            cfg.moe_shared_experts, cfg.moe_routed_scale, cfg.moe_scoring, cfg.moe_renorm_eps,
            cfg.moe_first_dense, cfg.activation, cfg.tie_embeddings) == (
        32, 32, 4, 1792, 0, 1.0, "sigmoid", 1e-6, 2, "swiglu", True)
    assert [cfg.layer_parts(i)[1] for i in range(24)] == ["mlp"] * 2 + ["moe"] * 22
    assert all(cfg.layer_rotates(i) for i in range(24)) and not any(cfg.layer_windows)
    assert cfg.num_params() == 8_339_930_560 and round(cfg.num_params() / 1e9, 2) == 8.34
    abstract = jax.eval_shape(whole.init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(abstract)) == cfg.num_params()
    assert abstract["layer_0"]["conv"]["in_proj"]["kernel"].shape == (2048, 6144)
    assert abstract["layer_2"]["moe"]["experts"]["up_proj"].shape == (32, 2048, 1792)
    config = cells.load_config("lfm2-8b-a1b")
    served = cells.build_model(config, dtype=jnp.bfloat16)
    assert served.cfg.layer_types == lfm2_layers("ccac" * 3)
    assert served.cfg.num_params() == 3_928_728_256 == config["sizes"]["parameters_here"]
    pool = jax.eval_shape(lambda: served.init_cache(64, 4096))
    kv = SlotKVCache(pool, 64, 4096, kinds=served.cache_kinds())
    assert kv.bytes_per_token() == 3 * 2048 == config["reference"]["kv_bytes_per_token"]
    assert kv.state_bytes_per_slot() == 9 * 8192 == config["reference"]["state_bytes_per_slot"]
    assert kv.capacity_bytes() == 64 * (4096 * 6144 + 73_728) == 1_615_331_328
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(pool)]
    assert shapes.count((64, 1, 2, 2048)) == 9 and shapes.count((64, 8, 4096, 128)) == 3
    assert len(shapes) == 12
