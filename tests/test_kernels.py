"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

These run the kernels in the Pallas interpreter on the CPU, asked for
explicitly (``interpret=True``); tests/test_tpu_compile.py compiles the
same kernels for a TPU v5e."""
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
