"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses here what interpret mode accepts: blocks that
break the (8, 128) tiling, blocks over the scoped VMEM limit, programs over
the device's memory. Nothing runs, so these say nothing about results or
times. This is the only file that describes a topology: libtpu may be
loaded by one process at a time, so the description happens in a fixture
of this file, in the worker that runs it.
"""
import dataclasses
import functools
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.membw import membw
from repro.kernels.tpu import LANE
from repro.kernels.vai import vai
from repro.models import decode as decode_mod
from repro.models import model as model_mod
from repro.models.transformer import Runtime
from repro.serving.engine import ContinuousEngine
from repro.tuning import FlashAttentionSpace, MembwSpace, VaiSpace

MiB = 1 << 20
V5E_VMEM = 128 * MiB
V5E_HBM = 16 * (1 << 30)


@pytest.fixture(scope="module")
def topo():
    """A v5e:2x2 described for the compiler. The persistent compilation
    cache is off meanwhile: a compile for a described chip can be written
    to it but not read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


@pytest.mark.parametrize("loopsize", [0, 64])
def test_vai_compiles_on_arrays_4x_vmem(one_chip, loopsize):
    x = _sds(one_chip, (4 * V5E_VMEM // (LANE * 4), LANE))
    _compile_kernel(functools.partial(vai, loopsize=loopsize), x, x, x)


@pytest.mark.parametrize("chunk_bytes,n_chunks", [
    (3 * MiB, 1),        # re-read from VMEM
    (192 * MiB, 2),      # the paper's largest chunk: tiled from HBM
])
def test_membw_compiles(one_chip, chunk_bytes, n_chunks):
    x = _sds(one_chip, (n_chunks * chunk_bytes // (LANE * 4), LANE))
    _compile_kernel(functools.partial(membw, n_chunks=n_chunks, n_iters=8),
                    x)


@pytest.mark.parametrize("head_dim", [160, 128])
def test_flash_attention_compiles_bf16(one_chip, head_dim):
    q = _sds(one_chip, (8, 4096, head_dim), jnp.bfloat16)
    _compile_kernel(functools.partial(flash_attention, causal=True), q, q, q)


@pytest.mark.parametrize("make_space", [
    VaiSpace, MembwSpace, FlashAttentionSpace,
    # lattices that cross the VMEM limit: their largest blocks are pruned
    lambda: VaiSpace(n_elems=1 << 23,
                     block_rows_options=(1024, 2048, 4096, 8192)),
    lambda: FlashAttentionSpace(batch_heads=2, seq_q=4096,
                                block_q_options=(512, 1024, 2048),
                                block_k_options=(512, 1024, 2048)),
], ids=["vai", "membw", "flash_attention", "vai-wide", "flash-wide"])
def test_every_kept_candidate_of_a_space_compiles(one_chip, make_space):
    """The spaces prune against the scoped VMEM limit the kernels compile
    with, so nothing they keep is refused by the compiler. Each program is
    the one ``WallClockBackend`` times: a Mosaic kernel, not the
    interpreter."""
    space = make_space()
    kept = space.candidates()
    assert kept
    for cand in kept:
        fn, args = space.program(cand, interpret=False)
        _compile_kernel(fn, *(_sds(one_chip, a.shape, a.dtype)
                              for a in args))


def test_stablelm_decode_step_compiles_at_full_width(one_chip):
    """The engine's decode step for stablelm-12b at its published widths
    (two of its 40 layers), 8 slots x 2048 positions, fits one v5e."""
    cfg = dataclasses.replace(get_config("stablelm-12b"), n_layers=2)
    rt = Runtime(tp=1)
    key = _sds(one_chip, (2,), jnp.uint32)
    params = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda k: model_mod.init_params(cfg, rt, k)[0], key))
    slots, max_len = 8, 2048
    state = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: decode_mod.init_decode_state(
            cfg, rt, slots, max_len)))
    vec = functools.partial(_sds, one_chip, (slots,))
    step = jax.jit(functools.partial(ContinuousEngine._step_impl,
                                     SimpleNamespace(cfg=cfg, rt=rt)),
                   donate_argnums=(1, 2, 3, 6))
    compiled = step.lower(params, state, vec(jnp.int32), vec(jnp.int32),
                          vec(jnp.float32), vec(jnp.bool_), key).compile()
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < resident < V5E_HBM
