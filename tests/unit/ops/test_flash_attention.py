"""Flash attention kernel numerics vs jnp reference (pattern: reference
tests/unit/ops kernel-vs-torch tolerance asserts). Runs interpreted on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, flash_attention_with_lse


def ref_scores(q, k, causal=True):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(d)
    T, S = q.shape[2], k.shape[2]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, S), bool))[None, None], s, -1e30)
    return s


def ref_attn(q, k, v, causal=True):
    """bhtd reference attention."""
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(ref_scores(q, k, causal), -1).astype(q.dtype),
                      v)


def make_qkv(T=256, B=2, H=4, D=64, dtype=jnp.float32, seed=0):
    rng = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(jax.random.fold_in(rng, i), (B, H, T, D), dtype) for i in range(3))


@pytest.mark.parametrize("causal", [True, False])
def test_forward(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_attn(q, k, v, causal)), atol=2e-5)


@pytest.mark.parametrize("T", [256, 200, 384, 640, 1000])
def test_gradients(T):
    """640 and 1000: the last block masked (the padded edge), the interior
    not."""
    q, k, v = make_qkv(T=T, B=1, H=2) if T > 384 else make_qkv(T=T)
    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, True, 128, 128)**2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(ref_attn(q, k, v)**2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("hkv", [1, 2])
def test_gqa_native(hkv):
    """K/V keep their grouped head count — fwd and grads match the expanded
    reference."""
    q, _, _ = make_qkv(T=256, H=4)
    _, k, v = tuple(x[:, :hkv] for x in make_qkv(T=256, H=4, seed=1))
    g = 4 // hkv
    kx, vx = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    out = flash_attention(q, k, v, True, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_attn(q, kx, vx, True)), atol=2e-5)
    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, True, 128, 128)**2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, kx, vx: jnp.sum(ref_attn(q, kx, vx)**2), argnums=(0, 1, 2))(q, kx, vx)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gr[0]), atol=2e-4)
    # reference grads are per expanded head; group-sum to compare
    B, _, T, D = q.shape
    np.testing.assert_allclose(np.asarray(gf[1]),
                               np.asarray(gr[1].reshape(B, hkv, g, T, D).sum(2)), atol=2e-4)
    np.testing.assert_allclose(np.asarray(gf[2]),
                               np.asarray(gr[2].reshape(B, hkv, g, T, D).sum(2)), atol=2e-4)


def test_in_model():
    """Model with attention_impl='flash' matches the xla path."""
    from deepspeed_tpu.models import get_model
    m_xla = get_model("tiny", dtype=jnp.float32, attention_impl="xla", max_seq_len=256)
    m_flash = get_model("tiny", dtype=jnp.float32, attention_impl="flash", max_seq_len=256,
                        attention_block_q=128, attention_block_kv=128)
    params = m_xla.init_params(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 256, (2, 256)).astype(np.int32)}
    la = m_xla.loss(params, batch, None)
    lb = m_flash.loss(params, batch, None)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-4)
    ga = jax.grad(lambda p: m_xla.loss(p, batch, None))(params)
    gb = jax.grad(lambda p: m_flash.loss(p, batch, None))(params)
    flat_a = jax.tree_util.tree_leaves(ga)
    flat_b = jax.tree_util.tree_leaves(gb)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def grads(attn, q, k, v, *args):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v, *args).astype(jnp.float32)**2),
                    argnums=(0, 1, 2))(q, k, v)


def assert_all_close(got, want, atol):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol)


@pytest.mark.parametrize("T", [512, 1024])
def test_mask_free_body(T):
    """Causal at sub-blocks of 128: most blocks run the body with no mask;
    forward and all three gradients against the reference."""
    q, k, v = make_qkv(T=T, B=1, H=2)
    p = fa.plan(T, T, 64, q.dtype, True, 128, 128)
    assert not p.fallback and all(kp.split and kp.masked_pct < 50 for kp in p[:3])
    assert (p.fwd.sub_q, p.fwd.sub_kv, p.fwd.grid) == (128, 128, T)
    out = flash_attention(q, k, v, True, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_attn(q, k, v)), atol=2e-5)
    assert_all_close(grads(flash_attention, q, k, v, True, 128, 128), grads(ref_attn, q, k, v), 2e-4)


@pytest.mark.parametrize("T,S", [(200, 200), (256, 384), (384, 256), (256, 200)])
def test_non_causal_edges(T, S):
    """Non-causal (``ops/spatial.py``, the ring's off-diagonal chunks): only
    the padded edge is masked; ``q_len != kv_len`` as the zigzag ring calls
    it."""
    q, _, _ = make_qkv(T=T)
    _, k, v = make_qkv(T=S, seed=1)
    out = flash_attention(q, k, v, False, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_attn(q, k, v, False)), atol=2e-5)
    assert_all_close(grads(flash_attention, q, k, v, False, 128, 128),
                     grads(ref_attn, q, k, v, False), 2e-4)


def test_head_size_128_scale_on_the_scores():
    """1/sqrt(128) is no power of two: the scale stays on the float32 scores."""
    q, k, v = make_qkv(T=384, B=1, H=2, D=128)
    assert not fa._prescaled(128**-0.5) and fa._prescaled(64**-0.5)
    out = flash_attention(q, k, v, True, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_attn(q, k, v)), atol=2e-5)
    assert_all_close(grads(flash_attention, q, k, v, True, 128, 128), grads(ref_attn, q, k, v), 2e-4)


@pytest.mark.parametrize("causal,T", [(True, 512), (False, 200)])
def test_with_lse_and_its_cotangent(causal, T):
    """lse against the reference's logsumexp, and a loss that reads BOTH
    outputs (the lse cotangent rides on delta through the dq and dk/dv
    loops)."""
    q, k, v = make_qkv(T=T, B=1, H=2)
    out, lse = flash_attention_with_lse(q, k, v, causal, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_attn(q, k, v, causal)), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(ref_scores(q, k, causal), -1)), atol=2e-5)

    def loss(attn):
        def f(q, k, v):
            o, l = attn(q, k, v)
            return jnp.sum(o**2) + jnp.sum(jnp.sin(l))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    got = loss(lambda q, k, v: flash_attention_with_lse(q, k, v, causal, 128, 128))
    want = loss(lambda q, k, v: (ref_attn(q, k, v, causal),
                                 jax.nn.logsumexp(ref_scores(q, k, causal), -1)))
    assert_all_close(got, want, 2e-4)


@pytest.mark.parametrize("hkv", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("T,block", [(200, 512), (333, 128)])
def test_statistics_rows_off_the_lane_tile(T, block, causal, hkv):
    """lse and delta cross HBM as rows of whole lane tiles: a length that is
    no multiple of 128 pads them (200 -> one block of 256, under the single
    masked body where causal; 333 -> three sub-blocks of 128). Gradients of both entry
    points against the float32 reference, the second with a non-zero lse
    cotangent riding on delta."""
    q, _, _ = make_qkv(T=T, B=1, H=4)
    _, k, v = tuple(x[:, :hkv] for x in make_qkv(T=T, B=1, H=4, seed=1))
    kx, vx = jnp.repeat(k, 4 // hkv, axis=1), jnp.repeat(v, 4 // hkv, axis=1)
    p = fa.plan(T, T, 64, q.dtype, causal, block, block)
    assert p.fwd.sub_q % fa.LANES == 0 and p.dq.sub_q == p.dkv.sub_q == p.fwd.sub_q
    assert bool(p.fallback) == (block == 512 and causal)

    def folded(grads_):  # the reference's per expanded head, summed onto the shared KV heads
        fold = lambda g: g.reshape(1, hkv, 4 // hkv, T, 64).sum(2)
        return grads_[0], fold(grads_[1]), fold(grads_[2])

    assert_all_close(grads(flash_attention, q, k, v, causal, block, block),
                     folded(grads(ref_attn, q, kx, vx, causal)), 2e-4)

    def loss(attn, *operands):
        def f(q, k, v):
            o, l = attn(q, k, v)
            return jnp.sum(o**2) + jnp.sum(jnp.sin(l))
        return jax.grad(f, argnums=(0, 1, 2))(*operands)

    out, lse = flash_attention_with_lse(q, k, v, causal, block, block)
    assert lse.shape == (1, 4, T) and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(ref_scores(q, kx, causal), -1)), atol=2e-5)
    got = loss(lambda q, k, v: flash_attention_with_lse(q, k, v, causal, block, block), q, k, v)
    want = loss(lambda q, k, v: (ref_attn(q, k, v, causal),
                                 jax.nn.logsumexp(ref_scores(q, k, causal), -1)), q, kx, vx)
    assert_all_close(got, folded(want), 2e-4)


@pytest.mark.parametrize("causal,T,S", [(True, 200, 200), (False, 333, 200), (False, 100, 384)])
def test_rows_that_attend_nothing(causal, T, S):
    """The lse the forward hands the backward is (B, H, q blocks, 1, sub_q),
    a q block's values a row, float32. A row that attended nothing would carry -inf there; the only
    rows that can are the padded ones (a logical row always sees key 0), and
    both backward kernels neutralise it: with -inf on the padded rows every
    gradient is finite, the same bits as with the forward's own values
    there, and the reference's."""
    q, _, _ = make_qkv(T=T, B=1, H=2)
    _, k, v = make_qkv(T=S, B=1, H=2, seed=1)
    do = make_qkv(T=T, B=1, H=2, seed=2)[0]
    p = fa.plan(T, S, 64, q.dtype, causal, 128, 128)
    blocks = -(-T // p.fwd.sub_q)
    out, lse = fa._flash_call(q, k, v, causal, 128, 128, None)
    assert lse.shape == (1, 2, blocks, 1, p.fwd.sub_q) and lse.dtype == jnp.float32
    assert blocks * p.fwd.sub_q > T and np.isfinite(np.asarray(lse)).all()
    nothing = lse.reshape(1, 2, -1).at[..., T:].set(-jnp.inf).reshape(lse.shape)
    got = fa._flash_bwd_impl(causal, 128, 128, None, (q, k, v, out, nothing), do)
    own = fa._flash_bwd_impl(causal, 128, 128, None, (q, k, v, out, lse), do)
    for a, b in zip(got, own):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, vjp = jax.vjp(lambda q, k, v: ref_attn(q, k, v, causal), q, k, v)
    assert_all_close(got, vjp(do), 2e-4)


def test_bf16_operands():
    """bf16 operands into every product at a training cell's head: against
    the float32 reference on the same (rounded) operands."""
    q, k, v = make_qkv(T=1024, B=1, H=2, dtype=jnp.bfloat16)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    out = flash_attention(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref_attn(*f32)), atol=2e-2)
    got, want = grads(flash_attention, q, k, v, True), grads(ref_attn, *f32)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        err = np.abs(np.asarray(a, np.float32) - np.asarray(b))
        assert err.max() <= 3e-2 * np.abs(np.asarray(b)).max()


def forced(p):
    """``p`` with the masked body on every block, sizes as they are."""
    return fa.Plan(*(kp._replace(split=False) for kp in p[:3]), "forced")


@pytest.mark.parametrize("causal,T,S", [(True, 512, 512), (True, 1000, 1000), (False, 384, 200)])
def test_masked_everywhere_gives_the_same_bits(causal, T, S):
    """The two bodies are one definition: at equal block sizes the masked
    body on every block and the split loops agree bit for bit at float32."""
    q, _, _ = make_qkv(T=T, B=1, H=2)
    _, k, v = make_qkv(T=S, B=1, H=2, seed=1)
    do = make_qkv(T=T, B=1, H=2, seed=2)[0]
    p = fa.plan(T, S, 64, q.dtype, causal, 128, 128)
    assert not p.fallback

    def run(p):
        out, lse = fa._flash_call(q, k, v, causal, 128, 128, None, plan_=p)
        return (out, lse) + fa._flash_bwd_impl(causal, 128, 128, None, (q, k, v, out, lse), do,
                                               plan_=p)

    for a, b in zip(run(p), run(forced(p))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# (grid, sub_q, sub_kv), computed %, masked % of forward, dq and dk/dv: the
# module docstring's table
CELL_PLANS = {
    1024: [((1024, 256, 256), 124.9, 40.0)] * 3,
    2048: [((2048, 256, 256), 112.4, 22.2)] * 3,
}


@pytest.mark.parametrize("T", sorted(CELL_PLANS))
def test_plan_shares_at_the_cells(T):
    """The sizes ``plan`` picks at the two training cells' shapes (head size
    64, bf16, the config's blocks of 512) and the shares that follow."""
    p = fa.plan(T, T, 64, jnp.bfloat16, True, 512, 512)
    assert not p.fallback
    for kp, (sizes, computed_pct, masked_pct) in zip(p[:3], CELL_PLANS[T]):
        assert (kp.grid, kp.sub_q, kp.sub_kv) == sizes and kp.split
        assert round(kp.computed_pct, 1) == computed_pct
        assert round(kp.masked_pct, 1) == masked_pct
    # the parent's 512 x 512 blocks computed 150% / 125% of the mask's scores
    assert p.computed_pct < {1024: 150, 2048: 125}[T]


@pytest.mark.parametrize("T,causal,why", [(128, True, "wholly inside"), (65536, True, "VMEM")])
def test_plan_falls_back_and_says_so(T, causal, why):
    """One sub-block (nothing lies wholly inside the mask) or a head over the
    VMEM budget keeps the single masked body on the caller's blocks."""
    p = fa.plan(T, T, 128, jnp.bfloat16, causal, 512, 512)
    assert why in p.fallback
    b = min(T, 512)
    for kp in p[:3]:
        assert (kp.grid, kp.sub_q, kp.sub_kv, kp.split) == (b, b, b, False)
        assert kp.masked_pct == 100.0


def test_tally_notes_a_traced_calls_plan():
    before = fa.traced()
    q, k, v = make_qkv(T=256, B=1, H=1)
    jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 128, 128)).lower(q, k, v)
    (new, ) = fa.traced()[len(before):]
    assert new.plan == fa.plan(256, 256, 64, q.dtype, True, 128, 128)
    assert 100 < new.plan.computed_pct < 150 and 0 < new.plan.masked_pct < 100
    # lse and delta, 256 float32 each: a row rests at its values, the column
    # it was at 128 times them
    assert (new.stats_bytes_at_rest, new.stats_bytes_values) == (2 * 256 * 4, 2 * 256 * 4)
    assert fa.stats_bytes((1, 1, 256, 1)) == (128 * 256 * 4, 256 * 4)


def test_engine_sets_the_flash_gauges(tmp_path):
    """A training step that traced its program says what its flash kernels
    compute, where telemetry is on."""
    import json

    import deepspeed_tpu
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.models import get_model
    from deepspeed_tpu.telemetry import set_sink

    comm._state["mesh"] = None
    model = get_model("tiny", dtype=jnp.float32, attention_impl="flash", max_seq_len=256,
                      attention_block_q=128, attention_block_kv=128)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, rng_seed=0,
        config={"train_batch_size": 8, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10**9,
                "telemetry": {"enabled": True, "output_path": str(tmp_path / "tel")}})
    try:
        rng = np.random.default_rng(0)
        for _ in range(2):  # the second step traces nothing and sets nothing
            engine.train_batch(batch={"input_ids": rng.integers(0, 256, (8, 256)).astype(np.int32)})
        engine.telemetry.close()
    finally:
        set_sink(None)
    with open(engine.telemetry.jsonl_path) as f:
        gauges = [ev for ev in map(json.loads, f) if ev["type"] == "gauge"]
    call = fa.traced()[-1]
    p = call.plan
    assert not p.fallback and p.fwd.sub_q == 128
    for name, want in (("kernels/flash_scores_computed_pct", p.computed_pct),
                       ("kernels/flash_scores_masked_pct", p.masked_pct),
                       ("kernels/flash_stats_bytes_at_rest", call.stats_bytes_at_rest),
                       ("kernels/flash_stats_bytes_values", call.stats_bytes_values)):
        got = [ev["value"] for ev in gauges if ev["name"] == name]
        assert got == [pytest.approx(want)]
