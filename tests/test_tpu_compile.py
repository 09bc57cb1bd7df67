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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention
from repro.kernels import flash_attention as flash_attention_mod
from repro.kernels.flash_attention import flash_attention
from repro.kernels.membw import membw
from repro.kernels.tpu import LANE
from repro.kernels.vai import vai
from repro.models import attention as attn
from repro.models import decode as decode_mod
from repro.models import model as model_mod
from repro.models.transformer import Runtime
from repro.serving.engine import decode_step
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
    q = _sds(one_chip, (1, 8, 1, 4096, head_dim), jnp.bfloat16)
    k = _sds(one_chip, (1, 8, 4096, head_dim), jnp.bfloat16)
    _compile_kernel(functools.partial(flash_attention, causal=True), q, k, k)


@pytest.mark.parametrize("q_heads,kv_heads,head_dim,blocks", [
    (32, 8, 128, (512, 512)),   # mistral-nemo-12b-l8.chat
    (48, 8, 128, (256, 512)),   # codestral-22b-l8.code
    (32, 8, 160, (256, 512)),   # stablelm-12b: heads of 1.25 lanes
    (40, 40, 128, (512, 512)),  # qwen1.5-32b: one query head a kv head
])
def test_prefill_flash_attention_compiles_bf16(one_chip, q_heads, kv_heads,
                                               head_dim, blocks):
    """The prefill's kernel at both cells' widths (and two others) over a
    page of 4096, at the blocks the route picks for them, with
    per-sequence lengths: the VMEM count never picks blocks the compiler
    refuses."""
    groups = q_heads // kv_heads
    assert flash_attention_mod.block_sizes(groups, head_dim, 2) == blocks
    q = _sds(one_chip, (1, kv_heads, groups, 4096, head_dim), jnp.bfloat16)
    k = _sds(one_chip, (1, kv_heads, 4096, head_dim), jnp.bfloat16)
    _compile_kernel(functools.partial(flash_attention, block_q=blocks[0],
                                      block_k=blocks[1]),
                    q, k, k, _sds(one_chip, (1,), jnp.int32))


@pytest.mark.parametrize("slots,max_len,q_heads,kv_heads,head_dim", [
    (16, 4608, 32, 8, 128),     # mistral-nemo-12b-l8.chat
    (16, 4224, 48, 8, 128),     # codestral-22b-l8.code
    (8, 2048, 32, 8, 160),      # stablelm-12b
    (16, 4096, 40, 40, 128),    # qwen1.5-32b: five blocks of 8 kv heads
    (16, 4608, 64, 8, 256),     # the VMEM limit shrinks the chunk to 384
])
def test_pool_decode_attention_compiles_bf16(one_chip, slots, max_len,
                                             q_heads, kv_heads, head_dim):
    chunk = decode_attention.kv_chunk(max_len, q_heads, kv_heads, head_dim,
                                      2)
    assert chunk
    stack = _sds(one_chip, (8, slots, max_len, kv_heads, head_dim),
                 jnp.bfloat16)
    _compile_kernel(
        functools.partial(decode_attention.pool_decode_attention,
                          scale=head_dim ** -0.5, chunk=chunk),
        _sds(one_chip, (slots, 1, q_heads, head_dim), jnp.bfloat16),
        stack, stack, _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (slots,), jnp.int32))


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


def _compile_decode_step(one_chip, cfg, slots, max_len):
    """The engine's decode step compiled for one v5e."""
    rt = Runtime(tp=1)
    key = _sds(one_chip, (2,), jnp.uint32)
    params = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda k: model_mod.init_params(cfg, rt, k)[0], key))
    state = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: decode_mod.init_decode_state(
            cfg, rt, slots, max_len)))
    vec = functools.partial(_sds, one_chip, (slots,))
    step = jax.jit(functools.partial(decode_step, cfg, rt),
                   donate_argnums=(1, 2, 3, 6))
    return step.lower(params, state, vec(jnp.int32), vec(jnp.int32),
                      vec(jnp.float32), vec(jnp.bool_), key).compile()


def test_stablelm_decode_step_compiles_at_full_width(one_chip):
    """The engine's decode step for stablelm-12b at its published widths
    (two of its 40 layers), 8 slots x 2048 positions, fits one v5e."""
    cfg = dataclasses.replace(get_config("stablelm-12b"), n_layers=2)
    compiled = _compile_decode_step(one_chip, cfg, 8, 2048)
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < resident < V5E_HBM


def test_prefill_compiles_with_the_flash_kernel(one_chip, monkeypatch):
    """The engine's prefill program at a page of 4096 (two layers at
    codestral-22b's widths: 48 x 128 heads, 8 kv heads) with its attention
    selected as the flash kernel and compiled for the TPU: one kernel, in
    the layer scan, and the program fits one v5e."""
    monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    kernel = flash_attention_mod.flash_attention
    monkeypatch.setattr(
        flash_attention_mod, "flash_attention",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": False}))
    cfg = dataclasses.replace(get_config("stablelm-12b"), n_layers=2,
                              n_heads=48, n_kv_heads=8, d_model=6144,
                              d_ff=16384, head_dim=128)
    rt = Runtime(tp=1)
    params = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda k: model_mod.init_params(cfg, rt, k)[0],
                       _sds(one_chip, (2,), jnp.uint32)))

    def prefill(p, tokens, lengths):
        return decode_mod.prefill(cfg, rt, p, {"tokens": tokens}, 4096,
                                  lengths=lengths)

    compiled = jax.jit(prefill).lower(
        params, _sds(one_chip, (1, 4096), jnp.int32),
        _sds(one_chip, (1,), jnp.int32)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes + mem.argument_size_in_bytes < V5E_HBM


@pytest.mark.parametrize("arch", ["stablelm-12b", "dbrx-132b",
                                  "qwen1.5-32b"])
def test_decode_step_hands_the_stack_to_the_pool_kernel(one_chip, arch,
                                                        monkeypatch):
    """Compiled with the pool kernel (selected and compiled for the TPU
    here, where the backend is the CPU), the step gives the kernel the
    carried stacks themselves: no instruction makes an array of a layer's
    shape, in any order of its dims (the view XLA copied before the
    attention's dot), and none copies or transposes a stack; qwen1.5-32b's
    40 kv heads take the kernel too. Heads are 128 wide, as in the benchmark's
    cells: at stablelm-12b's own 160 the compiler lays the stack out with
    the positions minor and copies it in and out of the step on either
    path."""
    monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    kernel = decode_attention.pool_decode_attention
    monkeypatch.setattr(
        decode_attention, "pool_decode_attention",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": False}))
    cfg = dataclasses.replace(get_config(arch), n_layers=2, head_dim=128)
    slots, max_len = 8, 2048
    hlo = _compile_decode_step(one_chip, cfg, slots, max_len).as_text()
    stack = [2, slots, max_len, cfg.n_kv_heads, cfg.resolved_head_dim]
    layer = sorted(stack[1:])
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1
    operands = kernels[0].split("operand_layout_constraints=")[1]
    assert operands.count(f"[{','.join(map(str, stack))}]") == 2
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (\S+)\(",
                     line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        assert sorted(dims) != layer, line
        if dims == stack:
            assert m.group(2) not in ("copy", "transpose"), line
