"""The slot-pool decode attention kernel against the dense decode attention
it replaces, in the Pallas interpreter on the CPU (``interpret=True``);
tests/test_tpu_compile.py compiles it for a TPU v5e.

Every cache position at or past a slot's extent holds NaN for the kernel,
while the reference reads the same stack with those positions intact: a
finite, equal result shows that no dead position reaches the result, neither
from a chunk past the last live one nor from the last live chunk's tail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (kv_chunk, kv_head_block,
                                            pool_decode_attention,
                                            vmem_bytes)
from repro.kernels.tpu import VMEM_LIMIT_BYTES
from repro.models.attention import _dense_decode_attend

L, M, HD = 3, 640, 128
KC = 128                                    # the kv chunk at M
#: one slot per case: a single position, either side of a chunk edge, the
#: whole cache, and a stale slot left by a finished request (its output is
#: computed and thrown away, its rows past the extent dead as any other)
EXTENTS = [1, KC - 1, KC, KC + 1, M, 2 * KC + 37]


def test_kv_chunk_divides_the_cache_in_lane_multiples():
    assert kv_chunk(M, 8, 2, HD, 4) == KC
    assert kv_chunk(4608, 32, 8, 128, 2) == 512   # mistral-nemo-12b-l8.chat
    assert kv_chunk(4224, 48, 8, 128, 2) == 384   # codestral-22b-l8.code
    assert kv_chunk(2048, 32, 8, 160, 2) == 512   # stablelm-12b
    assert kv_chunk(24, 4, 2, 16, 4) == 24        # under one lane: one chunk


def test_kv_chunk_fits_vmem_or_is_zero():
    """Blocks of at most 8 kv heads, whatever the model has; a chunk whose
    grid step would pass the VMEM limit is passed over, and where none fits
    or no block tiles the kv heads the kernel is off (chunk 0)."""
    assert [kv_head_block(h) for h in (1, 2, 8, 16, 40, 12)] == [
        1, 2, 8, 8, 8, 0]
    assert kv_chunk(4096, 40, 40, 128, 2) == 512  # qwen1.5-32b: 5 blocks
    assert kv_chunk(4608, 64, 8, 256, 2) == 384   # 512 passes the limit
    assert kv_chunk(4096, 48, 12, 128, 2) == 0    # 12 kv heads: no block
    assert kv_chunk(4096, 32, 8, 4096, 2) == 0    # no chunk fits
    assert (vmem_bytes(384, 8, 8, 256, 2) <= VMEM_LIMIT_BYTES
            < vmem_bytes(512, 8, 8, 256, 2))


def _stack(key, dtype, n_kv):
    """K and V stacks whose layers differ, and the same stacks with every
    position at or past each slot's extent set to NaN."""
    kk, kv = jax.random.split(key)
    k = jax.random.normal(kk, (L, len(EXTENTS), M, n_kv, HD), jnp.float32)
    v = jax.random.normal(kv, (L, len(EXTENTS), M, n_kv, HD), jnp.float32)
    # layers far apart: a wrong layer index cannot pass for the right one
    k = (k + 3.0 * jnp.arange(L)[:, None, None, None, None]).astype(dtype)
    v = (v - 2.0 * jnp.arange(L)[:, None, None, None, None]).astype(dtype)
    dead = (jnp.arange(M)[None, :] >= jnp.asarray(EXTENTS)[:, None])
    dead = dead[None, :, :, None, None]
    return k, v, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("n_kv,groups", [
    (2, 4), (2, 6),         # Mistral-Nemo's and Codestral's groups
    (16, 1),                # MHA as qwen1.5-32b: two blocks of 8 kv heads
])
def test_pool_kernel_matches_dense_decode_attention(n_kv, groups, layer,
                                                    dtype, tol):
    key = jax.random.PRNGKey(n_kv * 100 + groups * 10 + layer)
    k, v, k_dead, v_dead = _stack(key, dtype, n_kv)
    q = jax.random.normal(jax.random.fold_in(key, 7),
                          (len(EXTENTS), 1, n_kv * groups, HD)).astype(dtype)
    extent = jnp.asarray(EXTENTS, jnp.int32)
    scale = HD ** -0.5
    got = pool_decode_attention(q, k_dead, v_dead, jnp.int32(layer), extent,
                                scale=scale, chunk=KC, interpret=True)
    want = _dense_decode_attend(q, k[layer], v[layer], extent, scale)
    assert got.shape == want.shape == (len(EXTENTS), 1, n_kv * groups, HD)
    assert got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
