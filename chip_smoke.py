"""Bring-up check: the paper's kernels and stablelm-12b serving on a TPU.

    python chip_smoke.py                # one chip: kernels, then serving
    python chip_smoke.py --four-chips   # four chips: stablelm-12b at tp=4
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced   # CPU rehearsal

Everything runs in this one process, which starts no other. Each phase
checks what it computed against a reference and raises on a mismatch;
nothing is caught. The last line of standard output is a JSON object
naming the device, printed only when every phase passed on a TPU. The
lines before it are named numbers: compile seconds per phase (XLA backend
compiles, persistent-cache reads included), first-run wall seconds (one
cold run including compilation and set-up, not a benchmark) and peak
device bytes.

``--reduced`` runs the same phases at a tiny size with the Pallas kernels
in interpret mode, then stops at the device gate without a result: a
rehearsal of the control flow on the CPU (add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for
``--four-chips``).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.hardware import ChipSpec, chip_for_device_kind  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.membw import membw  # noqa: E402
from repro.kernels.tpu import LANE  # noqa: E402
from repro.kernels.vai import vai  # noqa: E402
from repro.launch import serve as serve_launch  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import decode as decode_mod  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.models.common import default_rules  # noqa: E402
from repro.models.transformer import Runtime  # noqa: E402
from repro.parallel.sharding import named_sharding_tree  # noqa: E402

ARCH = "stablelm-12b"
GiB = 1 << 30
#: relative L2 error allowed between two bf16 evaluations of the same
#: logits (cache vs cache-free, tp=4 vs tp=1): a few bf16 roundings per
#: layer; a wrong position, mask or shard gives errors of order 1
LOGIT_REL_TOL = 5e-2
#: the engine's token must be the reference's argmax wherever the
#: reference's top-2 gap exceeds this share of the logit row's RMS: bf16
#: noise at LOGIT_REL_TOL-scale moves the gap between two entries by about
#: a hundredth of the RMS, so a gap this wide is decided by the model
MARGIN_TOL = 0.1
#: bf16 flash attention vs the f32 oracle (the tolerance of
#: tests/test_kernels.py for bf16)
FLASH_TOL = 2e-2


# ---------------------------------------------------------------- reporting
class Phase:
    """Times one phase and counts the XLA backend-compile seconds in it."""

    compile_s = 0.0

    @classmethod
    def _listen(cls, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cls.compile_s += duration

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        print(f"== phase {self.name}", flush=True)
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            log(f"{self.name}.compile_s", Phase.compile_s - self.c0)
            log(f"{self.name}.first_run_wall_s",
                time.perf_counter() - self.t0)


def log(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def log_peak_bytes(prefix: str, devices) -> None:
    for d in devices:
        stats = d.memory_stats()
        log(f"{prefix}.peak_bytes_in_use[{d.id}]",
            stats["peak_bytes_in_use"] if stats else "not reported")


def rel_l2(a: jax.Array, b: jax.Array) -> float:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ------------------------------------------------------------------ kernels
def _run_kernel(name: str, fn, args, interpret: bool):
    """Compile ``fn`` ahead of time (checking that the lowered program holds
    the Mosaic kernel) and run it once."""
    lowered = jax.jit(fn).lower(*args)
    if not interpret:
        check("tpu_custom_call" in lowered.as_text(),
              f"{name}: no tpu_custom_call in the lowered program")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    log(f"kernels.{name}.compile_s", time.perf_counter() - t0)
    out = compiled(*args)
    jax.block_until_ready(out)
    return out


def _ints(key, shape, hi):
    """Integer-valued f32 data: every sum and product the kernels form is
    exact, so kernel and oracle must agree bit for bit."""
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, hi).astype(
        jnp.float32))(key)


def phase_kernels(reduced: bool, vmem_bytes: int) -> None:
    interpret = reduced
    key = jax.random.PRNGKey(0)

    # vai: three inputs and the output each 4x the chip's VMEM
    rows = 1024 if reduced else 4 * vmem_bytes // (LANE * 4)
    log("kernels.vai.array_bytes", rows * LANE * 4)
    a, b, c = (_ints(jax.random.fold_in(key, i), (rows, LANE), 5)
               for i in range(3))
    for L in (0, 64):
        out = _run_kernel(f"vai_L{L}", functools.partial(
            vai, loopsize=L, interpret=interpret), (a, b, c), interpret)
        check(bool(jnp.array_equal(out, ref.vai_ref(a, b, c, L))),
              f"vai loopsize {L} differs from kernels.ref")
    del a, b, c, out

    # membw: a one-chunk working set re-read from VMEM, and two 192 MiB
    # chunks streamed from HBM tile by tile (the paper's largest chunk)
    for regime, chunk_bytes, n_chunks in (
            ("vmem", 3 << 20, 1), ("hbm", 192 << 20, 2)):
        if reduced:
            chunk_bytes //= 16
        rows = chunk_bytes // (LANE * 4)
        x = _ints(jax.random.fold_in(key, 7), (n_chunks * rows, LANE), 4)
        log(f"kernels.membw_{regime}.working_set_bytes",
            n_chunks * chunk_bytes)
        out = _run_kernel(f"membw_{regime}", functools.partial(
            membw, n_chunks=n_chunks, n_iters=8, interpret=interpret),
            (x,), interpret)
        check(bool(jnp.array_equal(out, ref.membw_ref(x, n_chunks, 8))),
              f"membw ({regime}) differs from kernels.ref")
    del x, out

    # flash attention at stablelm-12b's head_dim, bf16, causal
    bh, seq = (2, 256) if reduced else (8, 4096)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, 10 + i),
                                 (bh, seq, 160), jnp.bfloat16)
               for i in range(3))
    out = _run_kernel("flash_attention", functools.partial(
        flash_attention, causal=True, interpret=interpret), (q, k, v),
        interpret)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.attention_ref)(q.astype(jnp.float32),
                                          k.astype(jnp.float32),
                                          v.astype(jnp.float32))
    err = jnp.abs(out.astype(jnp.float32) - want)
    log("kernels.flash_attention.max_abs_err", float(jnp.max(err)))
    check(bool(jnp.all(err <= FLASH_TOL + FLASH_TOL * jnp.abs(want))),
          f"flash attention differs from kernels.ref beyond {FLASH_TOL}")


# ------------------------------------------------------------------ serving
def _cached_vs_cache_free(cfg, params, prompt: np.ndarray,
                          generated: np.ndarray):
    """Logits at the prompt's last position and at 4 decode steps, once
    through prefill + decode_step (per-sequence positions, as the engine
    decodes) and once through a cache-free forward of the same tokens."""
    rt = Runtime(tp=1)
    L = len(prompt)

    @jax.jit
    def cached(p, toks, nxt):
        logits, st = decode_mod.prefill(cfg, rt, p, {"tokens": toks}, L + 8)
        out = [logits[:, 0]]
        for i in range(4):
            logits, st = decode_mod.decode_step(
                cfg, rt, p, nxt[None, i:i + 1], jnp.full((1,), L + i), st)
            out.append(logits[:, 0])
        return jnp.stack(out, axis=1)[0, :, :cfg.vocab_size]

    @jax.jit
    def cache_free(p, toks):
        # forward_logits feeds tokens[:, :-1]: append one unused token
        logits = model_mod.forward_logits(cfg, rt, p, {"tokens": toks})
        return logits[0, L - 1:L + 4, :cfg.vocab_size]

    seq = np.concatenate([prompt, generated[:4], generated[:1]])
    return (cached(params, jnp.asarray(prompt)[None],
                   jnp.asarray(generated[:4])),
            cache_free(params, jnp.asarray(seq)[None]))


def _engine_vs_reference(cfg, params, reqs, outputs):
    """A cache-free forward over each request's prompt followed by the
    tokens the engine emitted for it. At the position before each emitted
    token: whether every logit is finite, the reference's argmax, and its
    top-2 gap in units of the row's RMS. One compile: every sequence is
    padded at its end, which causal attention never reads back."""
    rt = Runtime(tp=1)
    n_new = max(len(o) for o in outputs)
    P = max(len(r.prompt) + len(o) for r, o in zip(reqs, outputs))

    @jax.jit
    def ref(p, toks, L):
        # forward_logits feeds toks[:, :-1]; row j predicts emitted token j
        logits = model_mod.forward_logits(cfg, rt, p, {"tokens": toks})[0]
        rows = jax.lax.dynamic_slice_in_dim(logits, L - 1, n_new, axis=0)
        rows = rows[:, :cfg.vocab_size].astype(jnp.float32)
        top2 = jax.lax.top_k(rows, 2)[0]
        rms = jnp.sqrt(jnp.mean(jnp.square(rows), axis=-1))
        return (jnp.all(jnp.isfinite(rows)), jnp.argmax(rows, axis=-1),
                (top2[:, 0] - top2[:, 1]) / rms)

    for r, out in zip(reqs, outputs):
        seq = np.zeros((1, P + 1), np.int32)
        seq[0, :len(r.prompt)] = r.prompt
        seq[0, len(r.prompt):len(r.prompt) + len(out)] = out
        finite, top, gap = jax.device_get(
            ref(params, jnp.asarray(seq), jnp.int32(len(r.prompt))))
        yield bool(finite), top[:len(out)], gap[:len(out)]


def phase_serving(reduced: bool) -> None:
    full = get_config(ARCH)
    cfg = dataclasses.replace(full.reduced() if reduced else full,
                              n_layers=2 if reduced else 16)
    log("reduced", f"n_layers {full.n_layers}->{cfg.n_layers}"
        + (" (and widths: --reduced)" if reduced else ""))
    size = (dict(requests=4, slots=2, max_len=128, prompt_lens=(8, 64),
                 new_tokens=6) if reduced else
            dict(requests=16, slots=8, max_len=2048, prompt_lens=(64, 1024),
                 new_tokens=16))
    engine, reqs, rep = serve_launch.run(cfg, rate=0.5, seed=0, **size)
    log("serving.requests", len(reqs))
    log("serving.prompt_lens", [len(r.prompt) for r in reqs])
    log("serving.decode_steps", rep.n_steps)
    log("serving.tokens_out", rep.tokens_out)
    log("serving.occupancy_mean", rep.occupancy_mean)
    check(rep.occupancy_mean > 1, "no two requests shared the slot pool")
    for r, out in zip(reqs, rep.outputs):
        want = min(r.max_new_tokens, size["max_len"] - len(r.prompt))
        check(out is not None and len(out) == want,
              f"a request got {None if out is None else len(out)} tokens, "
              f"expected {want}")
        check(bool(np.all((out >= 0) & (out < cfg.vocab_size))),
              "a token id outside the vocabulary")

    # what the engine emitted, against a cache-free forward of the same
    # tokens: every request, so also those admitted while others decoded
    n_tokens = n_decided = 0
    for i, (finite, top, gap) in enumerate(_engine_vs_reference(
            cfg, engine.params, reqs, rep.outputs)):
        check(finite, f"request {i}: a reference logit was not finite")
        decided = gap > MARGIN_TOL
        wrong = np.flatnonzero(decided & (top != rep.outputs[i]))
        check(wrong.size == 0,
              f"request {i}: the engine emitted {rep.outputs[i][wrong]} at "
              f"steps {wrong}, the reference's argmax is {top[wrong]} "
              f"(top-2 gap {gap[wrong]} x RMS)")
        n_tokens += len(top)
        n_decided += int(decided.sum())
    log("serving.engine_tokens_checked_vs_reference",
        f"{n_decided} of {n_tokens} (top-2 gap > {MARGIN_TOL} x RMS)")
    check(4 * n_decided >= n_tokens,
          f"only {n_decided} of {n_tokens} reference argmaxes are clear of "
          f"bf16 noise: the token check says too little")

    i = int(np.argmin([len(r.prompt) for r in reqs]))
    got, want = _cached_vs_cache_free(cfg, engine.params, reqs[i].prompt,
                                      rep.outputs[i])
    check(bool(jnp.all(jnp.isfinite(got)) & jnp.all(jnp.isfinite(want))),
          "reference logits not finite")
    errs = [rel_l2(got[j], want[j]) for j in range(5)]
    log("serving.cache_vs_cache_free_rel_l2 [prefill, decode 1-4]", errs)
    check(max(errs) <= LOGIT_REL_TOL,
          f"cached logits differ from the cache-free forward: {errs}")

    dev = jax.devices()[0]
    log_peak_bytes("serving", [dev])
    stats = dev.memory_stats()
    if stats:
        log("serving.bytes_limit", stats["bytes_limit"])
        headroom = stats["bytes_limit"] - stats["peak_bytes_in_use"]
        log("serving.headroom_bytes", headroom)
        check(headroom >= 2 * GiB, "less than 2 GiB of device memory left")


# --------------------------------------------------------------- four chips
def _greedy_decode(prefill, decode, params, tokens, steps, forced=None):
    """Prefill a batch, then ``steps`` lock-step decode steps; feeds
    ``forced`` tokens when given (so two runs see the same inputs)."""
    logits, st = prefill(params, {"tokens": tokens})
    outs, toks = [logits], []
    for i in range(steps):
        tok = (jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
               if forced is None else forced[i])
        toks.append(tok)
        logits, st = decode(params, tok, jnp.int32(tokens.shape[1] + i), st)
        outs.append(logits)
    return outs, toks


def phase_four_chips(reduced: bool) -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
          f"found {len(devices)}")
    full = get_config(ARCH)
    base = full.reduced() if reduced else full
    mesh = make_host_mesh(1, 4)
    rules = default_rules()
    rt1, rt4 = Runtime(tp=1), Runtime(tp=4, mesh=mesh)
    B, S, M, steps = 4, (16 if reduced else 128), 256, 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                base.vocab_size)

    def jitted(cfg, rt):
        return (jax.jit(steps_mod.make_prefill_step(cfg, rt, M, rules)),
                jax.jit(steps_mod.make_decode_step(cfg, rt, rules)))

    # 1. two layers at full widths: tp=4 against tp=1 on device 0
    with Phase("four_chips_compare"):
        cfg = dataclasses.replace(base, n_layers=2)
        log("reduced", f"n_layers {full.n_layers}->2 (tp=4 vs tp=1)")
        p1 = jax.jit(lambda k: model_mod.init_params(cfg, rt1, k)[0])(
            jax.random.PRNGKey(0))
        ref_logits, toks = _greedy_decode(*jitted(cfg, rt1), p1, tokens,
                                          steps)
        with mesh:
            p4 = jax.device_put(p1, named_sharding_tree(
                model_mod.param_specs(cfg, rt4, rules), mesh))
            del p1
            tp4_logits, _ = _greedy_decode(*jitted(cfg, rt4), p4, tokens,
                                           steps, forced=toks)
        del p4
        errs = [rel_l2(a, b) for a, b in zip(tp4_logits, ref_logits)]
        log("four_chips.tp4_vs_tp1_rel_l2 [prefill, decode 1-4]", errs)
        check(max(errs) <= LOGIT_REL_TOL,
              f"tp=4 logits differ from tp=1: {errs}")

    # 2. all 40 layers at tp=4, drawn straight into their shards
    with Phase("four_chips_full"):
        cfg = base
        specs = model_mod.param_specs(cfg, rt4, rules)
        with mesh:
            params = jax.jit(
                lambda k: model_mod.init_params(cfg, rt4, k, rules)[0],
                out_shardings=named_sharding_tree(specs, mesh))(
                    jax.random.PRNGKey(0))
            outs, _ = _greedy_decode(*jitted(cfg, rt4), params, tokens,
                                     steps)
        check(all(bool(jnp.all(jnp.isfinite(o))) for o in outs),
              "a 40-layer tp=4 logit was not finite")
        total = sum(x.nbytes for x in jax.tree.leaves(params))
        log("four_chips.param_bytes_total", total)
        for d in devices:
            held = sum(s.data.nbytes for x in jax.tree.leaves(params)
                       for s in x.addressable_shards if s.device == d)
            log(f"four_chips.param_bytes[{d.id}]", held)
            check(0.9 * total / 4 <= held <= total / 2,
                  f"device {d.id} holds {held} of {total} param bytes: "
                  f"not a quarter share")
        log_peak_bytes("four_chips", devices)


# --------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the stablelm-12b tp=4 phase")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal: tiny sizes, interpret mode, "
                         "no result")
    args = ap.parse_args()

    log("compile_cache_dir", use_compile_cache())
    jax.monitoring.register_event_duration_secs_listener(Phase._listen)
    dev = jax.devices()[0]
    log("device", f"{dev.platform} {dev.device_kind!r} "
        f"x{len(jax.devices())}")
    spec = None if args.reduced else check_gate(dev)
    if args.four_chips:
        phase_four_chips(args.reduced)
    else:
        with Phase("kernels"):
            phase_kernels(args.reduced, spec.vmem_bytes if spec else 0)
        with Phase("serving"):
            phase_serving(args.reduced)
    if args.reduced:
        check_gate(dev)
        sys.exit("--reduced is a rehearsal and prints no result")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


def check_gate(dev) -> ChipSpec:
    """The device gate: a TPU whose kind the repo knows, or no result."""
    if dev.platform != "tpu":
        sys.exit(f"device gate: JAX found {dev.platform!r}, not a TPU")
    spec = chip_for_device_kind(dev.device_kind)
    log("chip", spec.name)
    return spec


if __name__ == "__main__":
    main()
