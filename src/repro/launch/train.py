"""End-to-end training driver: energy-aware runtime + fault tolerance.

Per step: run the compiled train_step, feed its (measured or dry-run-derived)
roofline profile to the selected power policy through an ``EnergySession``,
record telemetry, checkpoint on the configured cadence, and watch for
stragglers. Restart resumes from the latest committed checkpoint with
byte-identical data-pipeline alignment.

CPU usage (reduced configs):
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-12b \
        --steps 30 --reduced --policy energy-aware
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES_BY_NAME, ShapeConfig, get_config
from repro.power import EnergySession, StepProfile, TPU_V5E
from repro.power.policies import PolicyLike
from repro.checkpoint import Checkpointer, restore
from repro.data import SyntheticPipeline
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as model_mod
from repro.models.transformer import Runtime
from repro.optim import OptConfig, init_opt_state


class StragglerWatchdog:
    """EWMA step-time tracker per host; hosts persistently beyond
    ``threshold`` x the fleet median are flagged for eviction at the next
    checkpoint boundary (the elastic path re-meshes without them)."""

    def __init__(self, threshold: float = 2.0, alpha: float = 0.3):
        self.threshold = threshold
        self.alpha = alpha
        self.ewma: Dict[int, float] = {}

    def record(self, host: int, step_time_s: float) -> None:
        prev = self.ewma.get(host, step_time_s)
        self.ewma[host] = (1 - self.alpha) * prev + self.alpha * step_time_s

    def stragglers(self) -> list:
        if len(self.ewma) < 2:
            return []
        med = float(np.median(list(self.ewma.values())))
        return [h for h, v in self.ewma.items()
                if v > self.threshold * med]


@dataclasses.dataclass
class TrainConfig:
    steps: int = 30
    ckpt_dir: Optional[str] = None
    ckpt_interval: int = 10
    policy: PolicyLike = None           # name, PowerPolicy object, or None
    governor: bool = False              # deprecated alias: policy="energy-aware"
    slowdown_budget: float = 0.0
    freq_mhz: Optional[int] = None      # knob for policy="static"
    power_cap_w: Optional[float] = None  # knob for policy="power-cap"
    seed: int = 0
    log_every: int = 5

    def resolved_policy(self) -> PolicyLike:
        if self.policy is not None:
            return self.policy
        return "energy-aware" if self.governor else "nominal"


class Trainer:
    def __init__(self, cfg, shape: ShapeConfig, rt: Runtime,
                 opt_cfg: OptConfig = OptConfig(),
                 tcfg: TrainConfig = TrainConfig()):
        self.cfg, self.shape, self.rt = cfg, shape, rt
        self.opt_cfg, self.tcfg = opt_cfg, tcfg
        self.session = EnergySession(
            policy=tcfg.resolved_policy(), chip=TPU_V5E, window_s=15.0,
            slowdown_budget=tcfg.slowdown_budget, freq_mhz=tcfg.freq_mhz,
            cap_w=tcfg.power_cap_w)
        self.telemetry = self.session.telemetry
        self.watchdog = StragglerWatchdog()
        self.checkpointer = (Checkpointer(tcfg.ckpt_dir, tcfg.ckpt_interval)
                             if tcfg.ckpt_dir else None)
        self.pipeline = SyntheticPipeline(cfg, shape, seed=tcfg.seed)
        self._step_fn = jax.jit(steps_mod.make_train_step(cfg, rt, opt_cfg),
                                donate_argnums=(0,))
        self.start_step = 0
        self.state = None
        self.history: list = []

    # ------------------------------------------------------------ lifecycle
    def init_or_restore(self) -> None:
        key = jax.random.PRNGKey(self.tcfg.seed)
        params, _ = model_mod.init_params(self.cfg, self.rt, key)
        state = {"params": params, "opt": init_opt_state(params)}
        if self.checkpointer is not None:
            latest = self.checkpointer.latest()
            if latest is not None:
                state = restore(self.checkpointer.dir, latest, state)
                self.start_step = latest
                print(f"[restart] resumed from step {latest}", flush=True)
        self.state = state

    def _device_batch(self, step: int) -> Dict:
        batch = self.pipeline.batch_at(step)
        return {k: jnp.asarray(v) for k, v in batch.items()}

    def _step_profile(self) -> StepProfile:
        # roofline profile for the step: on CPU the wall-clock is
        # meaningless for TPU power, so we synthesize the profile from the
        # model-flops at the reduced scale; launch on real hardware replaces
        # this with the dry-run-derived profile.
        from repro.core.roofline import model_flops
        flops = model_flops(self.cfg, self.shape) * 3  # fwd+bwd
        return StepProfile(
            compute_s=flops / TPU_V5E.peak_flops,
            memory_s=flops / TPU_V5E.peak_flops * 0.6,
            collective_s=0.0)

    def run(self) -> Dict:
        if self.state is None:
            self.init_or_restore()
        losses = []
        n_hosts = max(jax.process_count(), 1)
        profile = self._step_profile()
        energy_aware = self.session.policy.name != "nominal"
        for step in range(self.start_step, self.tcfg.steps):
            batch = self._device_batch(step)
            t0 = time.perf_counter()
            self.state, metrics = self._step_fn(self.state, batch)
            jax.block_until_ready(metrics["loss"])
            wall = time.perf_counter() - t0
            self.watchdog.record(jax.process_index() % n_hosts, wall)
            d = self.session.observe(step, profile, wall)
            loss = float(metrics["loss"])
            losses.append(loss)
            self.history.append({"step": step, "loss": loss, "wall_s": wall})
            if energy_aware:
                self.history[-1]["gov"] = {
                    "freq_mhz": d.freq_mhz, "savings_pct": d.savings_pct}
            if self.checkpointer is not None:
                self.checkpointer.maybe_save(step + 1, self.state)
            if step % self.tcfg.log_every == 0:
                extra = (f" f={d.freq_mhz}MHz sav={d.savings_pct:.1f}%"
                         if energy_aware else "")
                print(f"step {step:5d} loss {loss:.4f} "
                      f"wall {wall*1e3:.0f}ms{extra}", flush=True)
        if self.checkpointer is not None:
            self.checkpointer.maybe_save(self.tcfg.steps, self.state,
                                         force=True)
            self.checkpointer.wait()
        return {"losses": losses,
                "stragglers": self.watchdog.stragglers(),
                "energy_j": self.session.total_energy_j()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config (required off-TPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=10)
    ap.add_argument("--policy", default=None,
                    choices=["nominal", "static", "power-cap",
                             "energy-aware"],
                    help="power policy (see repro.power.POLICIES)")
    ap.add_argument("--governor", action="store_true",
                    help="deprecated: same as --policy energy-aware")
    ap.add_argument("--slowdown-budget", type=float, default=0.0)
    ap.add_argument("--freq-mhz", type=int, default=None,
                    help="set-point for --policy static")
    ap.add_argument("--power-cap-w", type=float, default=None,
                    help="cap for --policy power-cap")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    shape = SHAPES_BY_NAME[args.shape]
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
        shape = shape.reduced()
    rt = Runtime(tp=1, moe_impl="local")
    tcfg = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_interval=args.ckpt_interval,
                       policy=args.policy, governor=args.governor,
                       slowdown_budget=args.slowdown_budget,
                       freq_mhz=args.freq_mhz,
                       power_cap_w=args.power_cap_w, seed=args.seed)
    trainer = Trainer(cfg, shape, rt, tcfg=tcfg)
    out = trainer.run()
    print(f"final loss {out['losses'][-1]:.4f}  "
          f"energy {out['energy_j']/1e3:.1f} kJ  "
          f"stragglers {out['stragglers']}")


if __name__ == "__main__":
    main()
