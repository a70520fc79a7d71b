"""The paged decode/span attention kernel's walk (interpret mode on the CPU):
a row's live KV blocks are copied and computed in a loop inside the kernel,
the first block of the next row that attends anything in flight meanwhile.

Against a dense float32 reference over ragged ends (nothing, one key, a
block's last key, a block's first key, the whole pool), for packed and
split leaves, one column and spans, grouped queries, int8 K/V with row
scales, a two-extent chain and a lossy row beside an exact one; and the
walk's own properties: no block past a row's end is read, a row that
attends nothing between two that do leaves both right, and the host's count
of walked keys is the kernel's trip count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import decode_attention as da

S, BLOCK = 128, 32
# nothing, one key, block 0's last key, block 1's first key, the pool's length
ENDS = (0, 1, BLOCK, BLOCK + 1, S)
GEOMETRY = {  # (query heads, kv heads, head size, packed)
    "packed": (4, 4, 64, True),
    "split": (4, 4, 32, False),
    "grouped": (8, 2, 32, False),
}


def _pool(rng, n, nkv, D, int8):
    """K and V leaves of ``n`` pool rows (float32, or int8 with one scale a
    token row, as the int8 tier holds them) and the float32 values they
    stand for."""
    k = rng.standard_normal((n, nkv, S, D)).astype(np.float32)
    v = rng.standard_normal((n, nkv, S, D)).astype(np.float32)
    if not int8:
        return jnp.asarray(k), jnp.asarray(v), None, k, v
    scale = (np.abs(np.concatenate([k, v], -1)).max(axis=(1, 3), keepdims=True) / 127.0)
    scale = scale.astype(np.float16).astype(np.float32)  # (n, 1, S, 1), as stored
    k8, v8 = (np.clip(np.rint(x / scale), -127, 127).astype(np.int8) for x in (k, v))
    return (jnp.asarray(k8), jnp.asarray(v8), jnp.asarray(scale.astype(np.float16)),
            k8.astype(np.float32) * scale, v8.astype(np.float32) * scale)


def _reference(q, k, v, start, ends, ext=None, sink=None, window=None):
    """Dense float32 attention of each row's query columns over its logical
    keys: column ``j`` of row ``i`` sees positions ``[start_i, ends_i + j)``,
    less ``[sink_i, ends_i + j - window_i)`` where ``window_i > 0``; a row
    with ``ends_i <= start_i`` is zeros. ``q``: (B, H, T, D) numpy."""
    B, H, T, D = q.shape
    nkv = k.shape[1]
    out = np.zeros(q.shape, np.float32)
    for i in range(B):
        if ends[i] <= start[i]:
            continue
        chain = [i] if ext is None else [max(int(e), 0) for e in ext[i]]
        kk = np.concatenate([k[c] for c in chain], axis=1)  # (nkv, E * S, D)
        vv = np.concatenate([v[c] for c in chain], axis=1)
        pos = np.arange(kk.shape[1])[None, :]
        end_col = ends[i] + np.arange(T)[:, None]
        keep = (pos >= start[i]) & (pos < end_col)
        if window is not None and window[i] > 0:
            keep &= (pos < sink[i]) | (pos >= end_col - window[i])
        for h in range(H):
            s = q[i, h].astype(np.float32) @ kk[h // (H // nkv)].T / np.sqrt(D)
            s = np.where(keep, s, -np.inf)
            p = np.exp(s - s.max(axis=1, keepdims=True))
            out[i, h] = (p / p.sum(axis=1, keepdims=True)) @ vv[h // (H // nkv)]
    return out


def _call(q, k, v, start, ends, span, packed, **kw):
    """The kernel through its public entry points: ``span`` 0 is the
    one-column call, otherwise the span call (whose ``base`` is ``ends - 1``)."""
    if packed:
        k, v = jnp.concatenate([k, v], axis=-1), None
    start, ends = jnp.asarray(start, jnp.int32), jnp.asarray(ends, jnp.int32)
    if span == 0:
        return da.paged_decode_attention(q[:, :, 0], k, v, start, ends, block_kv=BLOCK,
                                         **kw)[:, :, None]
    return da.paged_span_attention(q, k, v, start, ends - 1, block_kv=BLOCK, **kw)


@pytest.mark.parametrize("operands", ["plain", "int8", "extents", "lossy"])
@pytest.mark.parametrize("span", [0, 1, 64], ids=["column", "span1", "span64"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_walk_equals_dense_reference(geometry, span, operands):
    H, nkv, D, packed = GEOMETRY[geometry]
    rng = np.random.default_rng(span + 7 * len(operands))
    B = len(ENDS)
    k, v, scale, kf, vf = _pool(rng, B + 1, nkv, D, operands == "int8")
    qdt = jnp.bfloat16 if operands == "int8" else jnp.float32
    q = jnp.asarray(rng.standard_normal((B, H, max(span, 1), D)), qdt)
    start = np.asarray([0, 0, 3, 0, 40], np.int32)
    ends = np.asarray(ENDS, np.int32)
    kw, ref_kw = {}, {}
    if operands == "int8":
        kw = {"k_scale": scale, "v_scale": scale}
    elif operands == "extents":
        # two extents a row over the six pool rows: ends reach into the second
        # extent (its first key, its last), one chain leaves it unreserved
        ext = np.asarray([[2, 4], [0, -1], [3, 1], [1, 0], [5, 2]], np.int32)
        ends = np.asarray([0, 1, S, S + 1, 2 * S], np.int32)
        kw, ref_kw = {"ext": jnp.asarray(ext)}, {"ext": ext}
    elif operands == "lossy":
        # rows 1 and 3 exact (window 0) beside lossy ones
        sink = np.asarray([0, 2, 4, 4, 42], np.int32)
        window = np.asarray([8, 0, 8, 0, 40], np.int32)
        kw = {"sink": jnp.asarray(sink), "window": jnp.asarray(window)}
        ref_kw = {"sink": sink, "window": window}
    if operands != "extents":
        k, v, kf, vf = k[:B], v[:B], kf[:B], vf[:B]
        if scale is not None:
            kw = {"k_scale": scale[:B], "v_scale": scale[:B]}
    got = np.asarray(_call(q, k, v, start, ends, span, packed, **kw).astype(jnp.float32))
    want = _reference(np.asarray(q.astype(jnp.float32)), kf, vf, start, ends, **ref_kw)
    assert np.isfinite(got).all()
    tol = 2e-2 if operands == "int8" else 2e-5
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    assert not got[0].any()  # ends == 0: nothing attended, zeros


@pytest.mark.parametrize("span", [0, 64], ids=["column", "span64"])
@pytest.mark.parametrize("geometry", ["packed", "split"])
def test_no_block_outside_a_rows_window_is_read(geometry, span):
    """Every block wholly past a row's causal end, and every block wholly
    before its first attendable key, filled with NaN: the output is the
    clean pool's, bit for bit (a masked NaN key would still poison ``p @ V``)."""
    H, nkv, D, packed = GEOMETRY[geometry]
    rng = np.random.default_rng(3)
    start = np.asarray([0, 0, 0, 70, 0], np.int32)
    ends = np.asarray([0, 1, BLOCK, 97, 60], np.int32)
    B = len(ends)
    k, v, _, _, _ = _pool(rng, B, nkv, D, False)
    q = jnp.asarray(rng.standard_normal((B, H, max(span, 1), D)), jnp.float32)
    clean = np.asarray(_call(q, k, v, start, ends, span, packed))
    width = max(span, 1)
    lo, hi = da._walk(start, ends, width, BLOCK, S // BLOCK, xp=np)
    poison = np.ones((B, 1, S, 1), bool)
    for i in range(B):
        poison[i, :, lo[i] * BLOCK:hi[i] * BLOCK] = False
    assert poison[0].all() and poison[3, 0, :64].all() and not poison[3, 0, 64:].any()
    kn, vn = (jnp.where(poison, jnp.nan, x) for x in (k, v))
    got = np.asarray(_call(q, kn, vn, start, ends, span, packed))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("head_blocks", [1, 2])
@pytest.mark.parametrize("ends", [(50, 0, 70), (0, 0, 50, 0, 0, 33, 0), (0, 0, 0), (1, 128, 0, 97)],
                         ids=["one_idle", "idle_runs", "all_idle", "long_short"])
def test_rows_that_attend_nothing_between_rows_that_do(monkeypatch, ends, head_blocks):
    """The first block of the next row that attends anything is started
    while this row's last is computed, over any run of rows that attend
    nothing and over the kv-head blocks of one row: every row is right."""
    H, nkv, D, packed = GEOMETRY["split"]
    if head_blocks > 1:
        pick = da._pick_blocks
        monkeypatch.setattr(da, "_pick_blocks",
                            lambda n, *a, **kw: (n // head_blocks, pick(n, *a, **kw)[1]))
    da._decode_jit.clear_cache()
    rng = np.random.default_rng(len(ends))
    B = len(ends)
    k, v, _, kf, vf = _pool(rng, B, nkv, D, False)
    q = jnp.asarray(rng.standard_normal((B, H, 1, D)), jnp.float32)
    start, ends = np.zeros(B, np.int32), np.asarray(ends, np.int32)
    got = np.asarray(_call(q, k, v, start, ends, 0, packed))
    da._decode_jit.clear_cache()
    np.testing.assert_allclose(got, _reference(np.asarray(q), kf, vf, start, ends),
                               atol=2e-5, rtol=2e-5)
    assert not got[ends == 0].any()


def test_walked_keys_is_the_kernels_trip_count():
    """``walked_keys`` (the scheduler's counter) against a hand count, and
    ``walk_block_kv`` against the blocks the call is built with."""
    start = np.asarray([0, 0, 0, 0, 300, 0], np.int32)
    ends = np.asarray([0, 1, 256, 257, 700, 1024], np.int32)
    # one column, 256-key blocks of a 1024-row pool: 0, 1, 1, 2, blocks 1-2, 4 blocks
    assert da.walked_keys(start, ends, 1, 256, 4) == 256 * (0 + 1 + 1 + 2 + 2 + 4)
    # a 64-column span reaches 63 keys further, never past the pool
    assert da.walked_keys(start, ends, 64, 256, 4) == 256 * (0 + 1 + 2 + 2 + 2 + 4)
    assert da.walked_keys(start, ends, 1, 128, 8) == 128 * (0 + 1 + 2 + 3 + 4 + 8)
    lo, hi = da._walk(jnp.asarray(start), jnp.asarray(ends), 1, 256, 4)
    np.testing.assert_array_equal(np.asarray(hi - lo), [0, 1, 1, 2, 2, 4])
    bf = jnp.bfloat16
    # the cells' shapes: a 256-key block of 20 packed heads or of 10 split
    # ones copies 1.3 MiB and stays; one of 30 split heads would copy 3.9 MiB
    assert da.walk_block_kv(20, 1, 64, 1024, 256, bf, bf, packed=True) == 256
    assert da.walk_block_kv(10, 4, 128, 4096, 256, bf, bf) == 256
    assert da.walk_block_kv(30, 1, 128, 1024, 256, bf, bf) == 128
    assert da.walk_block_kv(30, 1, 128, 1024, 128, bf, bf) == 128  # the caller's bound holds
    assert da.walk_block_kv(4, 1, 32, 128, 32, bf, bf) == 32       # no smaller aligned divisor
    for args in ((20, 1, 64, 1024, 256, bf, bf, False, True), (30, 1, 128, 1024, 256, bf, bf, False)):
        assert da.walk_block_kv(*args) == da._pick_blocks(*args)[1]
