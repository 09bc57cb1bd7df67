"""Sharded, jitted execution of the hot streaming-analysis path.

:class:`ShardedExecutor` runs the per-shard work of
:func:`repro.power.stream.replay` (profile inversion + batched policy
decisions) and of the streaming modal reduction on the jax backend, under
``shard_map`` across a device mesh — **bit-for-bit equal** to the numpy
single-stream path. Results stay exact because parity is engineered, not
hoped for (see docs/BACKENDS.md for the full story):

* every chip/policy constant is passed as a *runtime argument*, never
  baked into the jaxpr, so XLA cannot constant-fold a division into a
  reciprocal multiply or reassociate a constant product;
* the executable is compiled with ``xla_cpu_max_isa=AVX`` so LLVM cannot
  contract ``a*b+c`` into an FMA (AVX has no FMA instruction — 256-bit
  vectors are kept, only fused multiply-adds are off the table). jax
  0.9.0 ignores this per-compile option; there the process needs
  ``XLA_FLAGS=--xla_cpu_max_isa=AVX`` for parity;
* the one reused division in every ``(c/f)/t`` utilization chain sits
  behind ``lax.optimization_barrier`` so XLA's algebraic simplifier
  cannot combine the two divides into ``c/(f*t)``;
* ``x ** GAMMA`` — the only op whose libm differs between XLA and numpy
  — is hoisted to the host: frequency-grid pows are precomputed with
  numpy and passed in as vectors, and the pow of a *selected* frequency
  is obtained by running the same ``where``/``max`` selection over the
  pow grid (exact, because pow is monotone on the positive grid);
* the streaming-decompose segment sums emulate numpy's pairwise
  summation over each 128-sample segment (8-way unrolled accumulators,
  then the same combine tree), so segment values match ``np.sum`` bit
  for bit and the host-side left-to-right carry is unchanged.

``shard_map`` keeps all of this exact: each device applies the identical
elementwise program to a disjoint slice, and every cross-sample
reduction stays on the host in the original numpy order.

Throughput comes from three levers: per-shard fan-out across the mesh,
an internally chunked kernel whose temporaries stay small enough for the
allocator to recycle (see ``chunk=``), and — the big one on quantized
telemetry — collapsing each shard to its unique ``(power, mode)`` pairs
before the kernel and gathering the decisions back (``dedup=``), which
is exact because the kernel is elementwise in those inputs.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.power_model import (GAMMA, W_COMPUTE, W_MEMORY, W_NETWORK,
                                    ChipModel)

__all__ = ["ShardedExecutor"]

_SEG = 128                      # repro.core.modal.STREAM_SEGMENT
_N_MODES = 4

# Runtime-scalar pack layout: one (len(_SC),) float64 vector per kernel
# call, replicated across the mesh. Everything numeric the kernels need
# rides here so that swapping chips, caps, or budgets never recompiles.
_SC = ("eps6", "eps12", "one", "pow_one", "idle_r", "span_r", "idle_e",
       "span_e", "tdp_e", "w_c", "w_m", "w_n", "budget_mult", "one_eps9",
       "cap_w", "f_lo", "pow_lo", "f0", "pf0")
_IX = {k: i for i, k in enumerate(_SC)}


def _pack_scalars(rec: ChipModel, ev: ChipModel, **extra) -> np.ndarray:
    sc = np.zeros(len(_SC), dtype=np.float64)
    sc[_IX["eps6"]] = 1e-6
    sc[_IX["eps12"]] = 1e-12
    sc[_IX["one"]] = 1.0
    sc[_IX["pow_one"]] = np.asarray(1.0) ** GAMMA
    sc[_IX["idle_r"]] = rec.spec.idle_w
    sc[_IX["span_r"]] = rec.spec.tdp_w - rec.spec.idle_w
    sc[_IX["idle_e"]] = ev.spec.idle_w
    sc[_IX["span_e"]] = ev.spec.tdp_w - ev.spec.idle_w
    sc[_IX["tdp_e"]] = ev.spec.tdp_w
    sc[_IX["w_c"]] = W_COMPUTE
    sc[_IX["w_m"]] = W_MEMORY
    sc[_IX["w_n"]] = W_NETWORK
    sc[_IX["one_eps9"]] = 1.0 + 1e-9
    sc[_IX["f0"]] = 1.0
    sc[_IX["pf0"]] = np.asarray(1.0) ** GAMMA
    for k, v in extra.items():
        sc[_IX[k]] = v
    return sc


class ShardedExecutor:
    """Device-mesh executor for the streaming replay/decompose hot path.

    Parameters
    ----------
    devices:
        ``None`` (all of ``jax.devices()``), an int (the first N
        devices), or an explicit device sequence. On a CPU-only host,
        emulate a mesh with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
        *before* importing jax (docs/BACKENDS.md).
    chunk:
        Samples per kernel invocation. Shards larger than this are
        processed in ``chunk``-sized slices so XLA's preallocated
        temporaries stay below the glibc mmap-threshold cap and get
        recycled instead of faulted in fresh every call. 64k is the
        measured sweet spot on CPU; see docs/BACKENDS.md before tuning.
    dedup:
        ``"auto"`` (default) collapses a shard to its unique
        ``(power, mode)`` pairs when profitable — quantized telemetry
        (real sensors emit 0.1 W steps) typically has ~10x fewer unique
        powers than samples. ``True`` forces the attempt, ``False``
        disables it. Exact either way.
    isa:
        ``xla_cpu_max_isa`` compiler option (``"AVX"`` default — the
        parity requirement). ``None`` lets XLA use the full host ISA,
        which breaks bit-for-bit parity on FMA hardware; only use it
        when exactness does not matter.
    """

    def __init__(self, devices=None, *, chunk: int = 65536,
                 dedup="auto", isa: Optional[str] = "AVX"):
        import jax
        from jax.sharding import Mesh

        self._jax = jax
        if devices is None:
            devices = jax.devices()
        elif isinstance(devices, int):
            avail = jax.devices()
            if devices > len(avail):
                raise ValueError(
                    f"asked for {devices} devices but only {len(avail)} "
                    f"present; set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={devices} "
                    f"before importing jax (see docs/BACKENDS.md)")
            devices = avail[:devices]
        self.devices = list(devices)
        self.ndev = len(self.devices)
        self.mesh = Mesh(np.array(self.devices), ("shards",))
        self.chunk = int(chunk)
        self.dedup = dedup
        self.isa = isa
        self._compiled: Dict[tuple, Any] = {}
        self._memo: Dict[tuple, Any] = {}
        self.stats = {"kernel_calls": 0, "samples": 0, "dedup_samples": 0,
                      "compiles": 0, "memo_hits": 0}

    def __repr__(self) -> str:
        return (f"ShardedExecutor(ndev={self.ndev}, chunk={self.chunk}, "
                f"dedup={self.dedup!r}, isa={self.isa!r})")

    # --------------------------------------------------------------- policy
    def supports(self, policy) -> bool:
        """True when ``policy`` is one of the built-ins whose batched
        decision math this executor mirrors exactly. Third-party
        policies make :func:`repro.power.stream.replay` fall back to the
        numpy path (same results, single-stream speed)."""
        from repro.power.policies import (EnergyAwarePolicy, NominalPolicy,
                                          PowerCapPolicy,
                                          StaticFrequencyPolicy)
        return type(policy) in (NominalPolicy, StaticFrequencyPolicy,
                                PowerCapPolicy, EnergyAwarePolicy)

    # ----------------------------------------------------------- public API
    def decide_shard(self, policy, model: ChipModel, rec_model: ChipModel,
                     power_w: np.ndarray, mode_idx: Optional[np.ndarray],
                     duration_s: np.ndarray, freq_frac,
                     modes_from_power: bool = False,
                     return_modes: bool = False):
        """Replay's per-shard decision pass on the mesh: invert
        ``rec_model``'s power model into roofline profiles and evaluate
        ``policy`` on ``model`` — returns ``(energy_j, baseline_energy_j,
        time_s, mode_idx)`` arrays bit-for-bit equal to
        ``surf_rec.infer_profiles(...)`` + ``decide_batch(...)``.

        ``mode_idx=None`` band-classifies ``power_w`` against
        ``rec_model`` internally (cheap — on the deduplicated values
        only, when the dedup path applies); ``return_modes=True``
        appends that classified array to the return tuple so callers
        (``replay``'s recorded fold) never classify twice.
        """
        p = np.asarray(power_w, dtype=np.float64)
        m = None if mode_idx is None \
            else np.asarray(mode_idx, dtype=np.int64)
        if mode_idx is None:
            modes_from_power = True
        dur = np.broadcast_to(np.asarray(duration_s, dtype=np.float64),
                              p.shape)
        n = p.size
        f_scalar = np.ndim(freq_frac) == 0
        # the maxed recorded frequency and its pow, exactly as
        # infer_profiles computes them (host numpy — same libm)
        if f_scalar:
            fr = np.float64(np.maximum(np.float64(freq_frac), 1e-6))
            pfr = np.float64(np.asarray(fr) ** GAMMA)
        else:
            fr = np.maximum(np.asarray(freq_frac, dtype=np.float64), 1e-6)
            pfr = fr ** GAMMA
        kind, nf, sc, fgrid, pgrid = self._policy_setup(
            policy, model, rec_model)
        self.stats["samples"] += n

        elem = n and f_scalar and bool(np.all(dur == dur.flat[0]))
        if elem and self.dedup in ("auto", True):
            d0 = np.float64(dur.flat[0])
            # cross-shard memo over quantized keys: warm shards are pure
            # table gathers, no kernel launch at all
            out = self._memo_decide(kind, nf, sc, fgrid, pgrid, policy,
                                    model, rec_model, p, m, d0, fr, pfr,
                                    modes_from_power)
            if out is not None:
                return out if return_modes else out[:4]
            # per-shard dedup: unique (power, mode) pairs, gather back
            out = self._unique_decide(kind, nf, sc, fgrid, pgrid,
                                      rec_model, p, m, d0, fr, pfr,
                                      modes_from_power)
            if out is not None:
                return out if return_modes else out[:4]

        if m is None:
            m = np.asarray(_classify(p, rec_model.spec), dtype=np.int64)
        outs = [self._run_decide(kind, nf, p[s], m[s], dur[s],
                                 fr if f_scalar else fr[s],
                                 pfr if f_scalar else pfr[s],
                                 sc, fgrid, pgrid, dur_scalar=False,
                                 f_scalar=f_scalar)
                for s in _slices(n, self.chunk)]
        res = tuple(np.concatenate([o[i] for o in outs]) for i in range(4))
        return res + (m,) if return_modes else res

    # --------------------------------------------------- decision fast paths
    def _policy_setup(self, policy, model: ChipModel, rec_model: ChipModel):
        from repro.power.policies import (EnergyAwarePolicy, NominalPolicy,
                                          PowerCapPolicy,
                                          StaticFrequencyPolicy)
        if isinstance(policy, NominalPolicy):
            return "fixed", 0, _pack_scalars(rec_model, model), \
                np.zeros(1), np.zeros(1)
        if isinstance(policy, StaticFrequencyPolicy):
            f0 = model.freq_frac(policy.freq_mhz)
            sc = _pack_scalars(rec_model, model, f0=f0,
                               pf0=np.asarray(f0) ** GAMMA)
            return "fixed", 0, sc, np.zeros(1), np.zeros(1)
        if isinstance(policy, PowerCapPolicy):
            lo = model.f_min_frac
            i = np.arange(policy.grid + 1, dtype=np.float64)
            fgrid = lo + ((1.0 - lo) * i) / policy.grid
            pgrid = fgrid ** GAMMA          # vectorized: _pow_gamma(fgrid)
            sc = _pack_scalars(rec_model, model, cap_w=policy.cap_w,
                               f_lo=lo, pow_lo=np.asarray(lo) ** GAMMA)
            return "cap", policy.grid + 1, sc, fgrid, pgrid
        if isinstance(policy, EnergyAwarePolicy):
            fgrid = np.asarray(model.freq_grid(policy.n_freqs))
            # one 0-d pow per candidate — mirrors the numpy sweep, which
            # pows each python-float grid point separately
            pgrid = np.asarray([np.asarray(f) ** GAMMA for f in fgrid])
            sc = _pack_scalars(
                rec_model, model,
                budget_mult=1.0 + policy.slowdown_budget,
                cap_w=policy.power_cap_w
                if policy.power_cap_w is not None else 0.0)
            kind = ("sweep", policy.objective,
                    policy.power_cap_w is not None)
            return kind, policy.n_freqs, sc, fgrid, pgrid
        raise TypeError(
            f"unsupported policy {type(policy).__name__}; check "
            f"supports() before calling decide_shard")

    def _unique_decide(self, kind, nf, sc, fgrid, pgrid, rec_model,
                       p, m, d0, fr, pfr, modes_from_power):
        n = p.size
        if self.dedup == "auto" and n < 4096:
            return None
        if modes_from_power:
            uq, inv = np.unique(p, return_inverse=True)
            um = np.asarray(_classify(uq, rec_model.spec), dtype=np.int64)
        else:
            # complex packing sorts (power, mode) lexicographically
            uq_c, inv = np.unique(p + 1j * m, return_inverse=True)
            uq, um = uq_c.real, uq_c.imag.astype(np.int64)
        if self.dedup == "auto" and uq.size > n // 2:
            return None                     # not enough repetition to pay
        self.stats["dedup_samples"] += n
        be, bb, bt, bm = self._run_decide(kind, nf, uq, um, d0, fr, pfr,
                                          sc, fgrid, pgrid,
                                          dur_scalar=True)
        modes = m if m is not None else um[inv]
        return be[inv], bb[inv], bt[inv], bm[inv], modes

    def _memo_decide(self, kind, nf, sc, fgrid, pgrid, policy, model,
                     rec_model, p, m, d0, fr, pfr, modes_from_power):
        """Quantized-telemetry fast path: decisions are elementwise in
        ``(power, mode)``, and the jitted math is value-deterministic
        (exactly-rounded elementwise ops; pow precomputed on the host),
        so results memoize across shards. Powers map to integer keys at
        0.1 W (then 0.01 W) resolution; a shard only launches the kernel
        for keys never seen under this (policy, chips, duration, freq)
        signature — typically none after the first shard. Exactness is
        *checked*, not assumed: any key collision (two distinct floats,
        one bucket) disables the memo for good and falls back."""
        sig = (kind, nf, type(policy).__name__, policy,
               rec_model.spec, model.spec, float(d0), float(fr),
               modes_from_power)
        ent = self._memo.get(sig, None)
        if ent is False:
            return None                     # collided before: fallback
        if m is not None and (m.size == 0 or m.min() < 0 or m.max() >= 8):
            return None
        for scale in (10.0, 100.0):
            if ent is not None and ent["scale"] != scale:
                continue
            k = np.round(p * scale).astype(np.int64)
            if m is not None:
                k = k * 8 + m               # (power, mode) compound key
            if k.size == 0 or k.min() < 0 or k.max() >= (1 << 22):
                ent = None
                continue
            if ent is None:
                ent = {"scale": scale, "size": 0}
                self._memo[sig] = ent
            out = self._memo_run(ent, kind, nf, sc, fgrid, pgrid,
                                 rec_model, p, m, k, d0, fr, pfr,
                                 modes_from_power)
            if out is not None:
                return out
            self._memo[sig] = ent = None    # collision at this scale
        if ent is None:
            self._memo[sig] = False
        return None

    def _memo_run(self, ent, kind, nf, sc, fgrid, pgrid, rec_model,
                  p, m, k, d0, fr, pfr, modes_from_power):
        kmax = int(k.max())
        if kmax >= ent["size"]:
            grow = max(kmax + 1, 2 * ent["size"])
            for name, dt in (("have", bool), ("val", np.float64),
                             ("im", np.int64), ("be", np.float64),
                             ("bb", np.float64), ("bt", np.float64),
                             ("bm", np.int64)):
                new = np.zeros(grow, dtype=dt)
                if ent["size"]:
                    new[:ent["size"]] = ent[name]
                ent[name] = new
            ent["size"] = grow
        have = ent["have"][k]
        seen = k[have]
        if seen.size and not np.array_equal(ent["val"][seen], p[have]):
            return None                     # bucket collision: bail out
        fresh = ~have
        if fresh.any():
            kf, pf_ = k[fresh], p[fresh]
            tmp = ent["val"]                # scratch scatter, then verify
            tmp[kf] = pf_
            if not np.array_equal(tmp[kf], pf_) \
                    or not np.array_equal(tmp[k], p):
                return None                 # two floats in one bucket
            uqk = np.unique(kf)
            uq = ent["val"][uqk]
            if modes_from_power:
                um = np.asarray(_classify(uq, rec_model.spec),
                                dtype=np.int64)
            else:
                ent["im"][kf] = m[fresh]
                um = ent["im"][uqk]
            be, bb, bt, bm = self._run_decide(
                kind, nf, uq, um, d0, fr, pfr, sc, fgrid, pgrid,
                dur_scalar=True)
            ent["im"][uqk] = um
            ent["be"][uqk] = be
            ent["bb"][uqk] = bb
            ent["bt"][uqk] = bt
            ent["bm"][uqk] = bm
            ent["have"][uqk] = True
        else:
            self.stats["memo_hits"] += 1
        self.stats["dedup_samples"] += p.size
        modes = m if m is not None else ent["im"][k]
        return (ent["be"][k], ent["bb"][k], ent["bt"][k], ent["bm"][k],
                modes)

    def segment_sums(self, power_w: np.ndarray, modes: np.ndarray
                     ) -> np.ndarray:
        """The streaming-decompose inner kernel on the mesh: per-mode
        masked power sums (plus the all-samples total row) over each
        128-sample segment — ``(_N_MODES + 1, n // 128)``, each segment
        bit-for-bit ``np.sum`` of the same 128-vector. ``power_w`` must
        be segment-aligned (callers buffer, as ``_ModalAcc`` does)."""
        p = np.asarray(power_w, dtype=np.float64)
        m = np.asarray(modes, dtype=np.int64)
        nseg = p.size // _SEG
        if nseg * _SEG != p.size:
            raise ValueError(f"segment_sums needs a multiple of {_SEG} "
                             f"samples, got {p.size}")
        cap_seg = -(-nseg // self.ndev) * self.ndev
        cap = cap_seg * _SEG
        if cap != p.size:
            pp = np.zeros(cap)
            pp[:p.size] = p
            mm = np.zeros(cap, dtype=np.int64)
            mm[:m.size] = m
            p, m = pp, mm
        comp = self._segment_kernel(cap)
        import jax
        with jax.enable_x64(True):
            out = np.asarray(comp(p, m))
        self.stats["kernel_calls"] += 1
        return out[:, :nseg]

    # ---------------------------------------------------------- compilation
    def _capacity(self, n: int) -> int:
        base = _SEG * self.ndev
        cap = base
        while cap < n:
            cap *= 2
        return cap

    def _compile(self, kern, in_specs, args):
        import jax
        from repro.parallel.sharding import named_sharding_tree
        sm = jax.shard_map(kern, mesh=self.mesh, in_specs=in_specs,
                           out_specs=self._out_specs(kern))
        opts = {"xla_cpu_max_isa": self.isa} if self.isa else None
        with jax.enable_x64(True):
            comp = jax.jit(
                sm, in_shardings=named_sharding_tree(in_specs, self.mesh)
            ).lower(*args).compile(compiler_options=opts)
        self.stats["compiles"] += 1
        return comp

    def _out_specs(self, kern):
        from jax.sharding import PartitionSpec as P
        if getattr(kern, "_seg_out", False):
            return P(None, "shards")
        return (P("shards"),) * 4

    def _run_decide(self, kind, nf, p, m, dur, fr, pfr, sc, fgrid, pgrid,
                    dur_scalar: bool, f_scalar: bool = True):
        import jax
        from jax.sharding import PartitionSpec as P
        n = p.size
        cap = self._capacity(n)
        if cap != n:
            pad = cap - n
            p = np.concatenate([p, np.full(pad, sc[_IX["idle_r"]])])
            m = np.concatenate([m, np.ones(pad, dtype=np.int64)])
            if not dur_scalar:
                dur = np.concatenate([dur, np.ones(pad)])
            if not f_scalar:
                fr = np.concatenate([fr, np.ones(pad)])
                pfr = np.concatenate([pfr, np.ones(pad)])
        key = (kind, nf, cap, dur_scalar, f_scalar)
        comp = self._compiled.get(key)
        if comp is None:
            kern = _build_decide_kernel(kind, nf)
            shard = P("shards")
            specs = (shard, shard,
                     P() if dur_scalar else shard,
                     P() if f_scalar else shard,
                     P() if f_scalar else shard,
                     P(), P(), P())
            comp = self._compile(kern, specs,
                                 (p, m, dur, fr, pfr, sc, fgrid, pgrid))
            self._compiled[key] = comp
        with jax.enable_x64(True):
            out = comp(p, m, dur, fr, pfr, sc, fgrid, pgrid)
            out = [np.asarray(x) for x in out]
        self.stats["kernel_calls"] += 1
        return tuple(x[:n] for x in out)

    def _segment_kernel(self, cap: int):
        from jax.sharding import PartitionSpec as P
        key = ("segsum", cap)
        comp = self._compiled.get(key)
        if comp is None:
            kern = _build_segment_kernel()
            comp = self._compile(
                kern, (P("shards"), P("shards")),
                (np.zeros(cap), np.zeros(cap, dtype=np.int64)))
            self._compiled[key] = comp
        return comp


# ---------------------------------------------------------------------------
# Kernel bodies. Pure functions of runtime arrays only — see the module
# docstring for why no python-float constant may appear in the math.
# ---------------------------------------------------------------------------
def _classify(p, spec):
    from repro.core.modal import classify_power
    return classify_power(p, spec)


def _slices(n: int, chunk: int):
    return [slice(i, min(i + chunk, n)) for i in range(0, n, chunk)]


def _build_decide_kernel(kind, nf: int):
    import jax.numpy as jnp
    from jax import lax
    from repro.power.objectives import get_objective
    fence = lax.optimization_barrier
    objective, has_cap = "energy", False
    if isinstance(kind, tuple):
        kind, objective, has_cap = kind
    # registry scores are pure arithmetic (exactly-rounded products),
    # so the jnp and numpy evaluations agree bit for bit
    score = get_objective(objective).score

    def kern(p, m, dur, fr, pfr, sc, fgrid, pgrid):
        # ---- infer_profiles on the recording chip
        u = jnp.clip((p - sc[_IX["idle_r"]]) / sc[_IX["span_r"]], 0.0, None)
        wc = sc[_IX["w_c"]] * pfr
        is_cmp = m >= 3
        u_n = jnp.where(m == 1, 1.0, 0.0)
        u_m = jnp.where(m == 2, 1.0,
                        jnp.clip((u - sc[_IX["w_n"]] * u_n)
                                 / sc[_IX["w_m"]], 0.0, 1.0))
        u_m = jnp.where(is_cmp,
                        jnp.clip((u - wc) / sc[_IX["w_m"]], 0.0, 1.0), u_m)
        u_c = jnp.where(is_cmp, 1.0,
                        jnp.clip((u - sc[_IX["w_n"]] * u_n
                                  - sc[_IX["w_m"]] * u_m) / wc, 0.0, 1.0))
        c = u_c * fr * dur
        mm = u_m * dur
        nn = u_n * dur

        # ---- the evaluation chip's transfer surface
        def pw_t(ff, powf):
            f2 = jnp.maximum(ff, sc[_IX["eps6"]])
            t = jnp.maximum(jnp.maximum(c / f2, mm),
                            jnp.maximum(nn, sc[_IX["eps12"]]))
            q = fence(c / f2)           # keep (c/f)/t two divides
            pw = sc[_IX["idle_e"]] + sc[_IX["span_e"]] * (
                sc[_IX["w_c"]] * (q / t) * powf
                + sc[_IX["w_m"]] * (mm / t) + sc[_IX["w_n"]] * (nn / t))
            return jnp.minimum(pw, sc[_IX["tdp_e"]]), t

        pw0, t0 = pw_t(sc[_IX["one"]], sc[_IX["pow_one"]])
        e0 = pw0 * t0

        if kind == "fixed":
            pw, t = pw_t(sc[_IX["f0"]], sc[_IX["pf0"]])
            e = pw * t
        elif kind == "cap":
            # freq_for_power_cap: one argmax over the whole (n, grid+1)
            # plane; the selected frequency's pow rides the same mask
            # (exact — pow is monotone on the positive grid)
            F2 = jnp.maximum(fgrid, sc[_IX["eps6"]])
            T = jnp.maximum(jnp.maximum(c[:, None] / F2, mm[:, None]),
                            jnp.maximum(nn[:, None], sc[_IX["eps12"]]))
            Q = fence(c[:, None] / F2)
            PW = sc[_IX["idle_e"]] + sc[_IX["span_e"]] * (
                sc[_IX["w_c"]] * (Q / T) * pgrid
                + sc[_IX["w_m"]] * (mm[:, None] / T)
                + sc[_IX["w_n"]] * (nn[:, None] / T))
            PW = jnp.minimum(PW, sc[_IX["tdp_e"]])
            ok = PW <= sc[_IX["cap_w"]]
            fsel = jnp.max(jnp.where(ok, fgrid, sc[_IX["f_lo"]]), axis=-1)
            pfsel = jnp.max(jnp.where(ok, pgrid, sc[_IX["pow_lo"]]),
                            axis=-1)
            pw, t = pw_t(fsel, pfsel)
            e = pw * t
        else:                           # "sweep" (energy-aware)
            budget = t0 * sc[_IX["budget_mult"]]
            best_f = jnp.ones_like(t0)
            best_pf = jnp.full_like(t0, sc[_IX["pow_one"]])
            best_e = e0
            best_s = score(e0, t0, pw0)
            for i in range(nf):         # unrolled; candidates are runtime
                ff, powf = fgrid[i], pgrid[i]
                pw_i, t_i = pw_t(ff, powf)
                e_i = pw_i * t_i
                s_i = score(e_i, t_i, pw_i)
                ok = (s_i < best_s - sc[_IX["eps12"]]) \
                    & (t_i <= budget * sc[_IX["one_eps9"]])
                if has_cap:
                    ok = ok & (pw_i <= sc[_IX["cap_w"]])
                best_f = jnp.where(ok, ff, best_f)
                best_pf = jnp.where(ok, powf, best_pf)
                best_e = jnp.where(ok, e_i, best_e)
                best_s = jnp.where(ok, s_i, best_s)
            f2b = jnp.maximum(best_f, sc[_IX["eps6"]])
            t = jnp.maximum(jnp.maximum(c / f2b, mm),
                            jnp.maximum(nn, sc[_IX["eps12"]]))
            e = best_e

        # classify_mode_idx at nominal frequency
        qq = fence(c / jnp.maximum(sc[_IX["one"]], sc[_IX["eps6"]]))
        u_c0, u_m0, u_n0 = qq / t0, mm / t0, nn / t0
        mode = jnp.where(u_n0 >= jnp.maximum(u_c0, u_m0), 1,
                         jnp.where(u_m0 >= u_c0, 2, 3))
        return e, e0, t, mode

    return kern


def _build_segment_kernel():
    import jax.numpy as jnp

    def kern(p, m):
        midx = jnp.arange(1, _N_MODES + 1)
        sel = m[None, :] == midx[:, None]
        x = jnp.concatenate([p[None, :] * sel, p[None, :]], axis=0)
        # numpy pairwise summation over a 128 block: 8 accumulators fed
        # 8-at-a-time, then the fixed combine tree
        y = x.reshape(_N_MODES + 1, -1, _SEG // 8, 8)
        acc = y[:, :, 0, :]
        for i in range(1, _SEG // 8):
            acc = acc + y[:, :, i, :]
        s = ((acc[..., 0] + acc[..., 1]) + (acc[..., 2] + acc[..., 3])) \
            + ((acc[..., 4] + acc[..., 5]) + (acc[..., 6] + acc[..., 7]))
        return s

    kern._seg_out = True
    return kern
