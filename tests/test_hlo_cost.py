"""Trip-count-aware HLO cost analyzer: known-flops programs."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.hlo_cost import analyze_hlo


def _compiled(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_trip_count_multiplies_flops():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = _compiled(f, x, x)
    t = analyze_hlo(c.as_text())
    assert t.dot_flops == pytest.approx(10 * 2 * 512 ** 3, rel=1e-6)
    # XLA's own analysis undercounts by the trip count
    ca = c.cost_analysis()
    if isinstance(ca, list):        # older jaxlib returns [dict]
        ca = ca[0]
    assert ca["flops"] == pytest.approx(2 * 512 ** 3, rel=0.01)


def test_nested_scan_composes():
    def g(x, w):
        def inner(c, _):
            return c @ w, None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=5)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    t = analyze_hlo(_compiled(g, x, x).as_text())
    assert t.dot_flops == pytest.approx(15 * 2 * 256 ** 3, rel=1e-6)


def test_dus_charged_update_not_buffer():
    def h(cache, upd):
        return jax.lax.dynamic_update_slice(cache, upd, (0, 5, 0))

    cache = jax.ShapeDtypeStruct((4, 32768, 128), jnp.bfloat16)
    upd = jax.ShapeDtypeStruct((4, 1, 128), jnp.bfloat16)
    c = jax.jit(h, donate_argnums=0).lower(cache, upd).compile()
    t = analyze_hlo(c.as_text())
    cache_bytes = 4 * 32768 * 128 * 2
    assert t.bytes_accessed < cache_bytes / 100  # update-sized, not cache


def test_collective_bytes_counted():
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("x",))

    def g(a):
        return jax.lax.psum(a, "x")

    sm = jax.shard_map(g, mesh=mesh, in_specs=P(None, None),
                       out_specs=P(None, None))
    c = jax.jit(sm).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    t = analyze_hlo(c.as_text())
    # wire bytes are counted at bf16-equivalent width (the mixed-precision
    # model: CPU-XLA promotes bf16 math to f32, incl. collectives)
    assert t.collective_bytes.get("all-reduce") == 128 * 128 * 2
    assert t.collective_counts.get("all-reduce") == 1


def test_dot_flops_shape_table():
    def f(x, w):
        return x @ w
    x = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    t = analyze_hlo(_compiled(f, x, w).as_text())
    assert t.dot_flops == pytest.approx(2 * 64 * 32 * 16)
    assert len(t.dot_table) == 1
