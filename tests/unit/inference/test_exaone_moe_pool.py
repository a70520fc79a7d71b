"""An ``exaone_moe`` stack (K-EXAONE's kinds: windowed and full attention
layers 3 : 1 with rotation on the windowed ones only, per-head QK norm,
post-norm blocks, a leading dense layer under gated experts with a sigmoid
router, a multi-token-prediction module that drafts on the device) at a small
size on the CPU in float32: the program against the plain reference
(``chipbench/references/exaone_moe.py``: one causal forward with the module's
logits beside the stack's, no cache, a mask for the window), the slot pool's
span programs over rings, rows and the module's own rows with the drafter off
and on, what the drafter's roll-back leaves behind, the eight shares of a
layer, the refusals, and the sizes of the published preset.

Weights: the benchmark's own draw (``serve_ref.seeded_params``) with norm
scales moved off 1, so that a dropped or misplaced norm shows. ``TOL``: the
reference's float32 limit, 1e-5; the served path reads 1e-6 at worst; a wrong
ring row, window, rotation, span or void row gives 1e-3 and up."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import get_model
from deepspeed_tpu.models import transformer as tfm

from . import _ladder
from ._serving import VOCAB
from ._serving import prompts as _prompts

NAME = "tiny-exaone-moe"
ref, HP, TOL = _ladder.reference(NAME)
DRAFT = {"spec_tokens": 1, "spec_draft": "module"}  # the module drafts on the device


@pytest.fixture(scope="module")
def tiny():
    return _ladder.built(NAME)


def _tree(model, params):
    return _ladder.tree_of(NAME, model, params)


def _reference(eng, prompt, tokens):
    """The reference's logits and draft logits of the positions that chose
    ``tokens``."""
    ids = jnp.asarray([prompt + [int(t) for t in tokens]], jnp.int32)
    lg, dl, _ = ref.forward(_tree(eng.module, eng.params), ids, HP, first=len(prompt) - 1)
    return lg[0], dl[0]


class TestLadder(_ladder.Ladder):
    """The module's logits ride beside the stack's: both rungs of logits are
    this file's own."""
    twin = NAME

    def test_full_forward_matches_the_reference(self):
        """40 positions pass the 8-key window five times; the module's logits of
        every position but the last (which has no next token)."""
        model, params = _ladder.built(NAME)
        ids = jax.random.randint(jax.random.key(1), (2, 40), 0, VOCAB)
        with jax.default_matmul_precision("highest"):
            got, drafts = model.apply_with_mtp(params, ids)
        want, want_drafts, _ = ref.forward(_tree(model, params), ids, HP)
        for g, w in ((got, want), (drafts, want_drafts)):
            res = ref.compare(g[:, :-1].reshape(-1, VOCAB), w.reshape(-1, VOCAB), tol=TOL)
            assert res["ok"], res["error"]
        # the window and the rotation matter: a reference that drops them differs
        wrong = ref.forward(ref.from_tree(params, (0, ) * 5), ids, HP)[0]
        assert not ref.compare(got[:, :-1].reshape(-1, VOCAB), wrong.reshape(-1, VOCAB), tol=1e-3)["ok"]

    def test_served_path_matches_the_reference(self, case):
        """Prefill in chunks (a partial last one; 70 positions wrap the 8-row
        rings eight times; chunks of 12 straddle a ring's end), then 16 tokens
        through the pool, neighbours live in other slots, with the drafter off
        (one column a step; with the kernels injected a ring's one column goes
        through the paged kernel) and on (two columns a step, every draft of
        these random weights rejected, every step a roll-back): the stack's logits
        and, drafting, the module's beside them."""
        slots, chunk, steps, kernels, draft = case
        eng = _ladder.engine(NAME, slots, chunk, steps, kernels, fresh=True, **(DRAFT if draft else {}))
        sched = eng.scheduler()
        assert eng.model_config.attention_impl == ("flash" if kernels else "xla")
        prompts = _prompts((37, 70, 9))
        handles = [sched.submit(p, max_new_tokens=16, collect_logits=True) for p in prompts]
        sched.drain()
        for p, h in zip(prompts, handles):
            want, want_drafts = _reference(eng, p, h.result())
            res = ref.compare(h.result_logits(), want, tol=TOL)
            assert res["ok"] and res["rows"] == 16, res["error"]
            if draft:
                res = ref.compare(h.result_draft_logits(), want_drafts, tol=TOL)
                assert res["ok"] and res["rows"] == 16, res["error"]
                # the chosen experts of every committed position, the module's layer last
                assert h.result_choice().shape[0] == 5 and h.result_choice().shape[1] >= len(p) + 15
        assert sched.radix is None and sched.state_slots_reset == 3
        if draft:
            assert sched.drafter is None and sched.spec_drafted > 0
            assert sched.spec_rows_void == sched.spec_drafted - sched.spec_accepted
            # the pump kept running ahead: a device drafter reads nothing on the host
            assert sched.syncs_ahead > sched.syncs_serial


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_stream_with_the_drafter_is_the_stream_without_it(tiny, sampled):
    """Lossless: the same tokens, greedy and sampled, whatever the steps a
    sync and however the rows' budgets end inside a sync."""
    prompts = _prompts((5, 23, 40, 17), seed=2)
    kw = dict(do_sample=True, temperature=0.9, top_k=40, top_p=0.95) if sampled else {}

    def run(**cb):
        sched = _ladder.engine(NAME, slots=3, chunk=16, **cb).scheduler()
        hs = [sched.submit(p, max_new_tokens=13 + 3 * i, seed=11 + i, **kw)
              for i, p in enumerate(prompts)]
        sched.drain()
        return sched, [h.result().tolist() for h in hs]

    _, plain = run(steps=3)
    for steps in ((4, ) if sampled else (1, 4)):
        sched, drafted = run(steps=steps, **DRAFT)
        assert drafted == plain
        assert sched.cache.active_slots == 0 and not sched.active


def _agreeing(exact):
    """A one-layer stack and a module built to agree with it: the embedding's
    rows at unit mean square and ``W_eh`` passing the embedding alone, so
    that the module's input at position i IS the stack's at i + 1; its block
    and final norm copies of the stack's. ``exact``: attention's output
    projection at zero in both, so that a block is a function of its own
    position and the module's logits at i ARE the stack's at i + 1 (it never
    sees token 0, which attention would read). Otherwise the attention stays,
    damped by its post-norm's scale: the module is then nearly right."""
    cfg = dataclasses.replace(get_model("tiny-exaone-moe", dtype=jnp.float32).cfg, num_layers=1,
                              layer_types=("full_attention", ), layer_windows=(0, ),
                              moe_first_dense=0)
    model = type(get_model("tiny"))(cfg)
    p = _ladder.params_of(NAME, model, seed=5)
    emb = p["embed"]["embedding"]
    p["embed"]["embedding"] = emb * jax.lax.rsqrt(jnp.mean(jnp.square(emb), -1, keepdims=True))
    layer = p["layer_0"]
    if exact:
        layer["attn"]["o_proj"]["kernel"] = jnp.zeros_like(layer["attn"]["o_proj"]["kernel"])
    else:
        layer["attn_norm"]["scale"] = 0.5 * layer["attn_norm"]["scale"]
    H = cfg.hidden_size
    p["mtp"] = dict(p["mtp"], block=layer, final_norm=p["final_norm"],
                    enorm={"scale": jnp.ones(H)}, hnorm={"scale": jnp.ones(H)},
                    eh_proj={"kernel": jnp.concatenate([jnp.eye(H), jnp.zeros((H, H))])})
    return model, p


@pytest.mark.parametrize("exact", [True, False], ids=["always-right", "nearly-right"])
def test_a_drafter_that_agrees_commits_two_a_step(exact):
    """With drafts that are (nearly) always what the stack samples a row
    advances 2 a step, through the rows the accepted column wrote, and the
    stream is still the stream without the module."""
    tiny = _agreeing(exact)
    prompts = _prompts((6, 30, 19), seed=4)

    def run(draft):
        sched = _ladder.engine(NAME, slots=4, chunk=16, steps=4, twin=tiny,
                               **(DRAFT if draft else {})).scheduler()
        hs = [sched.submit(p, max_new_tokens=40, collect_logits=True) for p in prompts]
        sched.drain()
        return sched, [h.result().tolist() for h in hs], [h.result_logits() for h in hs]

    _, plain, plain_logits = run(False)
    sched, drafted, logits = run(True)
    assert drafted == plain
    for a, b in zip(logits, plain_logits):
        assert float(np.abs(a - b).max()) < 1e-4
    rate = sched.spec_accepted / sched.spec_drafted
    assert rate > 0.9 if exact else 0.2 < rate < 1.0, rate
    assert sched.mean_spec_tokens_per_step() > (1.6 if exact else 1.1), rate
    assert sched.spec_steps < 3 * 40  # fewer steps than tokens


def test_a_drafter_that_never_agrees_leaves_the_pool_as_a_run_without_it(tiny):
    """Every step writes a void column into four rings and two row caches and
    rolls it back by position: afterwards the full layer's rows and the ring
    rows every later query would read hold what a run without the module
    wrote, and nothing the module's own rows hold was written for a void
    column (they have no holes and end at the write head)."""
    prompt = _prompts((45, ), seed=9)[0]
    pools = {}
    for draft in (False, True):
        sched = _ladder.engine(NAME, slots=2, chunk=16, steps=4,
                               **(DRAFT if draft else {})).scheduler()
        # 24 tokens: 4 in the final chunk's sync, 20 in five more, none past the budget
        h = sched.submit(prompt, max_new_tokens=24)
        while not h.done:
            sched.step()
        sched.drain()
        pools[draft] = (jax.tree_util.tree_map(np.asarray, sched.cache.pool), h.result())
        if draft:
            assert sched.spec_accepted == 0 and sched.spec_rows_void == sched.spec_drafted
    (plain, toks), (drafted, toks_d) = pools[False], pools[True]
    assert toks.tolist() == toks_d.tolist()
    head = len(prompt) + 23  # rows written for committed columns: the last token's is not
    for comp in range(2):
        full = lambda pool: pool[comp][3][0, :, :head]
        np.testing.assert_allclose(full(drafted), full(plain), atol=1e-5)
        # a ring of 8: the rows of the last 8 committed positions are the window
        # of the next query, all but the one row a void column may have taken
        # (position head, which the next step overwrites before it reads)
        for layer in (0, 1, 2, 4):
            ring = lambda pool: pool[comp][layer][0]
            rows = [p % 8 for p in range(head - 7, head)]
            np.testing.assert_allclose(ring(drafted)[:, rows], ring(plain)[:, rows], atol=1e-5)
    assert not np.allclose(drafted[0][5][0, :, :head], 0.0)  # the module's rows, no holes
    assert np.count_nonzero(np.abs(drafted[0][5][0, :, :head]).sum(axis=(0, 2)) == 0) == 0


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """One expert layer cut eight ways (one expert of 8 a share): the shares'
    routed parts, with the shared expert and the attention half counted once,
    add up to what the uncut reference gives for the whole layer; and the
    program's share is the reference's share."""
    model, params = tiny
    lp = ref._block(params["layer_2"])
    x = jax.random.normal(jax.random.key(3), (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        wide = ref._widened(lp, 0.0)
        h = x + ref._rms(ref.attention(x, wide, HP, 8), wide["attn_ln"], HP["eps"])
        whole = ref.routed(h, lp, HP)[0]
        parts = []
        for share in range(8):
            one = dict(lp, **{k: lp[k][share:share + 1] for k in ("w_gate", "w_up", "w_down")})
            parts.append(ref.routed(h, one, HP, first=share)[0])
        assert float(jnp.abs(sum(parts) - whole).max()) < 1e-5
        assert float(jnp.abs(whole).max()) > 1e-3
        # the program holding experts 2-3 gives the reference's same share
        cfg = dataclasses.replace(model.cfg, moe_experts_held=2, moe_first_expert=2)
        held = dict(params["layer_2"]["moe"])
        held["experts"] = {k: v[2:4] for k, v in held["experts"].items()}
        from deepspeed_tpu.moe.layer import MoE
        got = MoE(cfg).apply({"params": held}, h, serving=True)
        two = dict(lp, **{k: lp[k][2:4] for k in ("w_gate", "w_up", "w_down")})
        want = ref.routed(h, two, dict(HP, first=2))[0] + ref._gated_ffn(
            h, wide["s_gate"], wide["s_up"], wide["s_down"])
        assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("span", [1, 2])
def test_a_window_of_128_under_a_256_row_kernel_block(span):
    """K-EXAONE's ring: 128 rows, under the paged kernel's 256-row block
    (``ring_rows`` rounds to 8). One column through the kernel (interpreted)
    and two through XLA's masked attention over [ring ; fresh rows] read what
    plain attention over the last 128 rotated keys reads, before and after
    the ring wraps."""
    cfg = dataclasses.replace(get_model("tiny-exaone-moe", dtype=jnp.float32).cfg,
                              layer_windows=(128, 128, 128, 0, 128), attention_impl="flash",
                              decode_block_kv=256)
    assert cfg.ring_rows(0) == 128
    B, nh, nkv, d = 3, 4, 2, 16
    keys = jax.random.split(jax.random.key(0), 5)
    heads = jnp.asarray([5, 127, 300])  # before the ring fills, at its end, after it wrapped
    total = int(heads.max()) + span
    k_all = jax.random.normal(keys[0], (B, nkv, total, d))
    v_all = jax.random.normal(keys[1], (B, nkv, total, d))
    q = jax.random.normal(keys[2], (B, nh, span, d))
    ring_k = jnp.zeros((B, nkv, 128, d))
    ring_v = jnp.zeros((B, nkv, 128, d))
    for b in range(B):
        for pos in range(int(heads[b])):
            ring_k = ring_k.at[b, :, pos % 128].set(k_all[b, :, pos])
            ring_v = ring_v.at[b, :, pos % 128].set(v_all[b, :, pos])
    fresh = lambda x: jnp.stack([x[b, :, int(heads[b]):int(heads[b]) + span] for b in range(B)])
    out, (rk, rv) = tfm._ring_attention(
        cfg, q, fresh(k_all), fresh(v_all), (ring_k, ring_v), heads,
        jnp.full((B, ), span, jnp.int32), 128, True, dict(block_kv=256, scale=d ** -0.5))
    for b in range(B):
        for j in range(span):
            pos = int(heads[b]) + j
            lo = max(0, pos - 127)
            keep = jnp.ones((1, 1, pos + 1 - lo), bool)
            want = tfm._grouped_attention_xla(q[b:b + 1, :, j:j + 1], k_all[b:b + 1, :, lo:pos + 1],
                                              v_all[b:b + 1, :, lo:pos + 1], keep, d ** -0.5,
                                              jnp.float32)
            assert float(jnp.abs(out[b, :, j] - want[0, :, 0]).max()) < 1e-5
            assert jnp.allclose(rk[b, :, pos % 128], k_all[b, :, pos])


def test_what_is_still_refused(tiny):
    cfg = tiny[0].cfg
    replace = lambda **kw: dataclasses.replace(cfg, **kw)
    with pytest.raises(ValueError, match="layer takes one"):
        dataclasses.replace(get_model("tiny-hybrid").cfg,
                            layer_windows=(8, 0, 0, 0))  # a linear-attention layer
    with pytest.raises(ValueError, match="rope or no positions"):
        replace(pos_embedding="alibi")
    with pytest.raises(ValueError, match="need layer_types"):
        dataclasses.replace(get_model("tiny").cfg, rope_windowed_only=True)
    with pytest.raises(ValueError, match="dropless dispatch only"):
        replace(moe_dropless=False, moe_scoring="softmax", moe_experts_held=None)
    with pytest.raises(ValueError, match="set qk_norm too"):
        replace(qk_norm=False)
    with pytest.raises(ValueError, match="moe_first_dense puts a dense MLP"):
        replace(moe_first_dense=5)
    with pytest.raises(ValueError, match="moe_first_dense puts a dense MLP"):
        dataclasses.replace(get_model("tiny").cfg, moe_first_dense=1)
    with pytest.raises(ValueError, match="one multi-token-prediction module at most"):
        replace(mtp_layers=2)
    with pytest.raises(ValueError, match="behind an unrolled stack"):
        dataclasses.replace(get_model("tiny").cfg, mtp_layers=1)
    with pytest.raises(NotImplementedError, match="no int8 tier"):
        tiny[0].cache_spec(2, 64, quantized=True)
    # a windowed layer's ring is served by spans, not by the static-batch cache paths
    with pytest.raises(NotImplementedError, match="span programs"):
        tiny[0].apply_with_cache(tiny[1], jnp.zeros((1, 4), jnp.int32),
                                 tiny[0].init_cache(1, 32), 0)


def test_a_module_drafter_needs_a_module():
    model = get_model("tiny", dtype=jnp.float32)
    eng = deepspeed_tpu.init_inference(model, config={
        "dtype": "float32", "max_out_tokens": 64,
        "continuous_batching": {"enabled": True, "num_slots": 2, "spec_tokens": 1,
                                "spec_draft": "module"}})
    with pytest.raises(ValueError, match="without a multi-token-prediction module"):
        eng.scheduler()


def test_published_preset_builds_48_layers_and_the_module():
    """Shapes only: 236.6 B parameters in the stack (47 sparse layers of 128
    experts, the dense layer, embedding and head), the module behind it with
    128 experts of its own; the cell's cut counts what ISSUE 41 counted."""
    big = get_model("k-exaone-236b-a23b")
    cfg = big.cfg
    shapes = jax.eval_shape(big.init_params, jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) - count(shapes["mtp"]) == 236_571_156_352
    assert count(shapes) == cfg.num_params()
    assert (cfg.num_layers, cfg.layer_windows[:8]) == (48, (128, 128, 128, 0) * 2)
    assert cfg.layer_parts(0) == ("full_attention", "mlp")
    assert cfg.layer_parts(1) == ("full_attention", "moe")
    assert [cfg.layer_rotates(i) for i in range(4)] == [True, True, True, False]
    assert cfg.ring_rows(0) == 128
    attn = count(shapes["layer_1"]["attn"])
    assert attn == 113_246_464 and count(shapes["layer_0"]) == 452_997_376
    assert count(shapes["layer_1"]["moe"]["experts"]) == 128 * 37_748_736
    cut = dataclasses.replace(cfg, num_layers=5, layer_types=("full_attention", ) * 5,
                              layer_windows=(128, 128, 128, 0, 128), moe_experts_held=16,
                              vocab_size=19200, max_seq_len=4096)
    cut_shapes = jax.eval_shape(type(big)(cut).init_params, jax.random.key(0))
    assert count(cut_shapes) == cut.num_params() == 4_543_318_144
    assert count(cut_shapes["mtp"]) == 831_289_728 and count(cut_shapes["layer_4"]) == 755_773_824
    kinds = type(big)(cut).cache_kinds()
    assert kinds[0] == ("ring", "ring", "ring", "rows", "ring", "rows")
