"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

These run the kernels in the Pallas interpreter on the CPU, asked for
explicitly (``interpret=True``); tests/test_tpu_compile.py compiles the
same kernels for a TPU v5e."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, settings, st  # hypothesis, or skip-stubs

from repro.kernels import ops, ref


@pytest.mark.parametrize("rows,block_rows", [(128, 128), (512, 256),
                                             (1024, 128)])
@pytest.mark.parametrize("loopsize", [0, 1, 2, 8, 32])
def test_vai_allclose(rows, block_rows, loopsize):
    key = jax.random.PRNGKey(rows + loopsize)
    a, b, c = [jax.random.normal(jax.random.fold_in(key, i), (rows, 128),
                                 jnp.float32) for i in range(3)]
    out = ops.vai_op(a, b, c, loopsize=loopsize, block_rows=block_rows,
                     interpret=True)
    expect = ref.vai_ref(a, b, c, loopsize)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(loopsize=st.integers(0, 48),
       log_rows=st.integers(7, 10))
def test_vai_property(loopsize, log_rows):
    rows = 2 ** log_rows
    key = jax.random.PRNGKey(loopsize * 101 + log_rows)
    a, b, c = [jax.random.normal(jax.random.fold_in(key, i), (rows, 128),
                                 jnp.float32) for i in range(3)]
    out = ops.vai_op(a, b, c, loopsize=loopsize, block_rows=128,
                     interpret=True)
    np.testing.assert_allclose(out, ref.vai_ref(a, b, c, loopsize),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("n_chunks,chunk_rows,n_iters",
                         [(4, 64, 9), (8, 32, 16), (2, 256, 5)])
def test_membw_allclose(n_chunks, chunk_rows, n_iters):
    key = jax.random.PRNGKey(n_chunks)
    x = jax.random.normal(key, (n_chunks * chunk_rows, 128), jnp.float32)
    out = ops.membw_op(x, n_chunks=n_chunks, n_iters=n_iters,
                       interpret=True)
    np.testing.assert_allclose(out, ref.membw_ref(x, n_chunks, n_iters),
                               rtol=1e-5, atol=1e-4)


def test_membw_tiles_a_chunk_larger_than_one_tile():
    """A chunk above MAX_TILE_BYTES is read as several tiles (the HBM
    regime on the chip); integer-valued input keeps the sums exact."""
    from repro.kernels.membw import MAX_TILE_BYTES, tile_rows
    chunk_rows = 2 * MAX_TILE_BYTES // (128 * 4)
    assert chunk_rows // tile_rows(chunk_rows) == 2
    x = np.random.default_rng(0).integers(
        0, 4, (2 * chunk_rows, 128)).astype(np.float32)
    out = ops.membw_op(x, n_chunks=2, n_iters=3, interpret=True)
    np.testing.assert_array_equal(out, ref.membw_ref(x, 2, 3))


def test_membw_tile_rows_rules():
    from repro.kernels.membw import tile_rows
    assert tile_rows(768) == 768                  # 384 KiB: one tile
    assert tile_rows(393216) == 8192              # 192 MiB: 48 tiles
    with pytest.raises(ValueError, match="sublane"):
        tile_rows(12)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Sq,Skv,Hq,Hkv,D,bq,bk", [
    (256, 256, 4, 4, 64, 128, 128),
    (256, 256, 4, 2, 64, 64, 128),     # GQA
    (128, 128, 2, 1, 128, 128, 64),    # MQA
    (512, 512, 2, 2, 64, 256, 256),
])
def test_flash_attention_allclose(Sq, Skv, Hq, Hkv, D, bq, bk, dtype):
    key = jax.random.PRNGKey(Sq + Hq)
    q = jax.random.normal(jax.random.fold_in(key, 0), (2, Sq, Hq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, Skv, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, Skv, Hkv, D), dtype)
    out = ops.flash_attention_op(q, k, v, causal=True, block_q=bq,
                                 block_k=bk, interpret=True)
    G = Hq // Hkv
    kk = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(2 * Hq, Skv, D)
    vv = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(2 * Hq, Skv, D)
    qq = q.transpose(0, 2, 1, 3).reshape(2 * Hq, Sq, D)
    expect = ref.attention_ref(qq, kk, vv, causal=True).reshape(
        2, Hq, Sq, D).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_flash_noncausal():
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, 128, 2, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 128, 2, 32))
    out = ops.flash_attention_op(q, k, v, causal=False, block_q=64,
                                 block_k=64, interpret=True)
    qq = q.transpose(0, 2, 1, 3).reshape(2, 128, 32)
    kk = k.transpose(0, 2, 1, 3).reshape(2, 128, 32)
    vv = v.transpose(0, 2, 1, 3).reshape(2, 128, 32)
    expect = ref.attention_ref(qq, kk, vv, causal=False).reshape(
        1, 2, 128, 32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The flash kernel's own layout: GQA blocks, causal and length skips
# ---------------------------------------------------------------------------
def _gqa_inputs(key, B, Hkv, G, S, D, dtype):
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, Hkv, G, S, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, D), dtype)
    return q, k, v


def _gqa_ref(q, k, v, b, length):
    """The oracle over sequence ``b``'s first ``length`` positions, each
    query head against its kv head's K and V (expanded here, for the
    oracle only): ``[Hkv, G, length, D]``."""
    _, Hkv, G, _, D = q.shape
    qq = q[b, :, :, :length].reshape(Hkv * G, length, D)
    kk = jnp.repeat(k[b, :, :length], G, axis=0)
    vv = jnp.repeat(v[b, :, :length], G, axis=0)
    f32 = [a.astype(jnp.float32) for a in (qq, kk, vv)]
    return ref.attention_ref(*f32, causal=True).reshape(Hkv, G, length, D)


@pytest.mark.parametrize("groups", [4, 6])
def test_flash_gqa_reads_each_kv_head_once(groups):
    """G = 4 and 6 query heads per kv head: the kernel takes K and V at
    their kv heads (nothing repeats them on the way in) and matches the
    oracle."""
    from repro.kernels.flash_attention import flash_attention
    q, k, v = _gqa_inputs(jax.random.PRNGKey(groups), 1, 2, groups, 256, 64,
                          jnp.float32)
    fn = functools.partial(flash_attention, block_q=128, block_k=128,
                           interpret=True)
    out = fn(q, k, v)
    np.testing.assert_allclose(out[0], _gqa_ref(q, k, v, 0, 256),
                               rtol=2e-5, atol=2e-5)
    # through the op, from the model's [B, S, H, D]: K and V enter the
    # kernel at Hkv heads and are never repeated or broadcast to Hq
    qs = q.transpose(0, 3, 1, 2, 4).reshape(1, 256, 2 * groups, 64)
    ks, vs = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    jaxpr = jax.make_jaxpr(functools.partial(
        ops.flash_attention_op, block_q=128, block_k=128, interpret=True))(
            qs, ks, vs).jaxpr
    eqns = jaxpr.eqns[0].params["jaxpr"].eqns       # inside the jit
    call = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(call) == 1
    assert [tuple(a.aval.shape) for a in call[0].invars[2:]] == [
        (1, 2, 256, 64)] * 2
    kv_size = ks.size
    for e in eqns:                  # nothing grows an array of K's size
        if e.primitive.name != "pallas_call" and any(
                a.aval.size == kv_size for a in e.invars
                if hasattr(a, "aval")):
            assert all(o.aval.size <= kv_size for o in e.outvars), e
    np.testing.assert_allclose(
        ops.flash_attention_op(qs, ks, vs, block_q=128, block_k=128,
                               interpret=True),
        out.transpose(0, 3, 1, 2, 4).reshape(1, 256, 2 * groups, 64),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("length", [1, 63, 64, 65, 256])
def test_flash_lengths_bound_both_axes(length, dtype):
    """With ``lengths`` the live rows (below each sequence's length) match
    the oracle over that sequence's live positions alone, whatever K and V
    hold past it (NaN here); q blocks wholly past the length are zeros.
    Lengths 1, bq - 1, bq, bq + 1 and the full page, beside a batch-mate
    at another length."""
    from repro.kernels.flash_attention import flash_attention
    S, bq = 256, 64
    q, k, v = _gqa_inputs(jax.random.PRNGKey(length), 2, 2, 4, S, 64, dtype)
    lengths = np.array([length, 150])
    dead = np.arange(S)[None, :] >= lengths[:, None]            # [B, S]
    k = jnp.where(dead[:, None, :, None], jnp.nan, k)
    v = jnp.where(dead[:, None, :, None], jnp.nan, v)
    out = flash_attention(q, k, v, jnp.asarray(lengths), block_q=bq,
                          block_k=128, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for b, L in enumerate(lengths):
        np.testing.assert_allclose(
            np.asarray(out[b, :, :, :L], np.float32),
            np.asarray(_gqa_ref(q, k, v, b, L), np.float32),
            rtol=tol, atol=tol)
        live_blocks = -(-L // bq) * bq
        assert np.all(np.asarray(out[b, :, :, live_blocks:]) == 0)
        assert np.all(np.isfinite(np.asarray(out[b], np.float32)))


@pytest.mark.parametrize("lengths,bq,bk", [
    ([256, 256], 64, 64),       # causal alone: the lower triangle
    ([1, 130], 64, 64),
    ([100, 256], 64, 128),      # kv blocks wider than q blocks
    ([200, 33], 128, 64),
])
def test_flash_computes_only_the_visited_blocks(lengths, bq, bk,
                                                monkeypatch):
    """The kernel reads a K block (so computes a block pair) exactly for
    the pairs the host's count gives: none past the diagonal, none past a
    sequence's length; a probe on the K block's load records them."""
    from jax.experimental import pallas as pl

    from repro.kernels import flash_attention as fa
    visits = []

    class Probe:
        def __init__(self, k_ref, ids):
            self._ref, self._ids = k_ref, ids

        def __getattr__(self, name):
            return getattr(self._ref, name)

        def __getitem__(self, idx):
            jax.debug.callback(lambda *ids: visits.append(
                tuple(int(i) for i in ids)), *self._ids)
            return self._ref[idx]

    kernel = fa._kernel

    def probed(len_ref, q_ref, k_ref, *rest, **kw):
        ids = [pl.program_id(i) for i in range(4)]
        return kernel(len_ref, q_ref, Probe(k_ref, ids), *rest, **kw)

    monkeypatch.setattr(fa, "_kernel", probed)
    S = 256
    q, k, v = _gqa_inputs(jax.random.PRNGKey(1), 2, 2, 2, S, 32,
                          jnp.float32)
    jax.block_until_ready(fa.flash_attention(
        q, k, v, jnp.asarray(lengths), block_q=bq, block_k=bk,
        interpret=True))
    assert len(visits) == len(set(visits))
    for b, L in enumerate(lengths):
        for h in range(2):
            mine = [(i, j) for bb, hh, i, j in visits if (bb, hh) == (b, h)]
            assert len(mine) == fa.visited_blocks(L, S, bq, bk)
            for i, j in mine:
                assert j * bk <= min(i * bq + bq - 1, L - 1)   # a live pair
