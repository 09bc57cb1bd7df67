"""XLA chunked attention: flash custom_vjp fwd/bwd vs naive; masking modes;
the decode attention's per-sequence length mask."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import _dense_decode_attend, chunked_attention

# long-running model/serving tests: fast lane skips these
pytestmark = pytest.mark.slow


def naive(q, k, v, causal=True, window=0, scale=None):
    B, Sq, Hq, Dk = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = scale or Dk ** -0.5
    kk = jnp.repeat(k, G, axis=2)
    vv = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    qpos, kpos = jnp.arange(Sq), jnp.arange(Skv)
    m = jnp.ones((Sq, Skv), bool)
    if causal:
        m &= kpos[None] <= qpos[:, None]
    if window:
        m &= kpos[None] > qpos[:, None] - window
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vv)


CASES = [
    dict(Sq=64, Hq=4, Hkv=2, Dk=16, Dv=16, win=0, qc=16, kc=16),
    dict(Sq=128, Hq=4, Hkv=1, Dk=32, Dv=16, win=0, qc=32, kc=64),
    dict(Sq=96, Hq=2, Hkv=2, Dk=16, Dv=16, win=24, qc=32, kc=16),
    dict(Sq=128, Hq=8, Hkv=8, Dk=8, Dv=8, win=0, qc=128, kc=128),
]


@pytest.mark.parametrize("case", CASES)
def test_forward_matches_naive(case):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, case["Sq"], case["Hq"], case["Dk"]))
    k = jax.random.normal(ks[1], (2, case["Sq"], case["Hkv"], case["Dk"]))
    v = jax.random.normal(ks[2], (2, case["Sq"], case["Hkv"], case["Dv"]))
    out = chunked_attention(q, k, v, causal=True, window=case["win"],
                            q_chunk=case["qc"], kv_chunk=case["kc"])
    np.testing.assert_allclose(
        out, naive(q, k, v, causal=True, window=case["win"]),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", CASES)
def test_flash_gradients_match_naive(case):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, case["Sq"], case["Hq"], case["Dk"]))
    k = jax.random.normal(ks[1], (2, case["Sq"], case["Hkv"], case["Dk"]))
    v = jax.random.normal(ks[2], (2, case["Sq"], case["Hkv"], case["Dv"]))

    def f(q, k, v):
        return chunked_attention(q, k, v, causal=True, window=case["win"],
                                 q_chunk=case["qc"],
                                 kv_chunk=case["kc"]).sum()

    def g(q, k, v):
        return naive(q, k, v, causal=True, window=case["win"]).sum()

    d1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    d2 = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(d1, d2):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_decode_path_valid_len_mask():
    """Decode attention with each sequence's own length ``valid [B]``:
    positions at or past a sequence's length are ignored regardless of their
    cache contents, for two sequences of different lengths."""
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16))
    k = jax.random.normal(ks[1], (2, 32, 2, 16))
    v = jax.random.normal(ks[2], (2, 32, 2, 16))
    valid = jnp.array([10, 23], jnp.int32)
    out = _dense_decode_attend(q, k, v, valid, 16 ** -0.5)
    k2 = k.at[0, 10:].set(999.0).at[1, 23:].set(999.0)
    v2 = v.at[0, 10:].set(-999.0).at[1, 23:].set(-999.0)
    out2 = _dense_decode_attend(q, k2, v2, valid, 16 ** -0.5)
    np.testing.assert_allclose(out, out2, rtol=1e-6, atol=1e-6)
