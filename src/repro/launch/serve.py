"""Serving driver: open-loop continuous batching with energy telemetry.

Seeded prompts arrive as a Poisson process (in decode-step units) and
:func:`repro.serving.serve` admits them into the free slots of a
:class:`~repro.serving.ContinuousEngine` between decode steps. The engine
serves the slot families (dense, moe); this launcher refuses the others
(ssm, hybrid, encdec, vlm), which ``ServeEngine.generate`` serves.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-12b \
        --reduced --requests 8 --new-tokens 8 --policy energy-aware

Without ``--reduced`` the model runs at its published widths in bf16;
``--layers`` cuts its depth to what the device holds.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as model_mod
from repro.models.transformer import Runtime
from repro.power import EnergySession
from repro.serving import (ContinuousEngine, Request, ServeReport,
                           poisson_arrivals, serve)
from repro.serving.engine import SLOT_FAMILIES


def make_requests(cfg: ModelConfig, n: int, prompt_lens: Tuple[int, int],
                  new_tokens: int, seed: int = 0) -> List[Request]:
    """``n`` prompts of random tokens, their lengths log-uniform over
    ``prompt_lens`` (inclusive): short prompts are the common case."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    lens = np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n)).astype(int)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, int(L),
                                        dtype=np.int32),
                    max_new_tokens=new_tokens) for L in lens]


def run(cfg: ModelConfig, *, requests: int = 16, slots: int = 8,
        max_len: int = 2048, prompt_lens: Tuple[int, int] = (64, 1024),
        new_tokens: int = 16, rate: float = 0.5, seed: int = 0,
        temperature: float = 0.0,
        session: Optional[EnergySession] = None
        ) -> Tuple[ContinuousEngine, List[Request], ServeReport]:
    """Build ``cfg`` on one device with random weights from ``seed`` and
    serve ``requests`` seeded prompts arriving at ``rate`` per decode step.
    Returns the engine, the requests and :func:`serve`'s report."""
    rt = Runtime(tp=1)
    # jitted so each weight is drawn and cast to the model dtype on the
    # device, without an f32 copy of the whole tree
    params = jax.jit(lambda k: model_mod.init_params(cfg, rt, k)[0])(
        jax.random.PRNGKey(seed))
    engine = ContinuousEngine(cfg, rt, params, max_slots=slots,
                              max_len=max_len, session=session, seed=seed)
    reqs = make_requests(cfg, requests, prompt_lens, new_tokens, seed)
    report = serve(engine, reqs, poisson_arrivals(requests, rate, seed),
                   temperature=temperature)
    return engine, reqs, report


def latencies_ms(rep: ServeReport) -> Dict[str, np.ndarray]:
    """From the report's records, in ms: each request's wait in the queue
    (due to admitted) and time to first token, and every gap between two
    consecutive tokens of one request."""
    t, r = rep.ticks, rep.requests
    stepped = r.first_tick >= 0
    gaps = [np.diff(t.read_s[a:b + 1]) for a, b in
            zip(r.first_tick[stepped], r.last_tick[stepped])]
    return {"queue wait": 1e3 * (r.admit_s - r.due_s),
            "TTFT": 1e3 * (t.read_s[r.first_tick[stepped]]
                           - r.due_s[stepped]),
            "ITL": 1e3 * np.concatenate([np.zeros(0)] + gaps)}


def slowest_tick(rep: ServeReport) -> Optional[str]:
    """The longest decode tick (from the previous step's tokens on the host
    to its own) and the phases that make it up."""
    t = rep.ticks
    if rep.n_steps < 2:
        return None
    k = int(np.argmax(np.diff(t.read_s))) + 1
    ms = {"tick": t.read_s[k] - t.read_s[k - 1],
          # the previous tick's bookkeeping, and any idle turns after it
          "book": t.start_s[k] - t.read_s[k - 1],
          "admit": t.admit_s[k], "step": t.step_s[k], "wait": t.wait_s[k]}
    ms = {name: 1e3 * v for name, v in ms.items()}
    other = ms["tick"] - sum(v for name, v in ms.items() if name != "tick")
    return (f"slowest tick {k}: {ms['tick']:.1f} ms = book {ms['book']:.1f}"
            f" + admit {ms['admit']:.1f} ({t.admitted[k]} requests, "
            f"{t.prompt_tokens[k]} prompt tokens) + step {ms['step']:.1f}"
            f" + wait {ms['wait']:.1f} ({t.active[k]} slots attending "
            f"{t.kv_positions[k]} positions) + other {other:.1f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny float32 config (CPU)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--prompt-lens", type=int, nargs=2, default=(64, 1024),
                    metavar=("MIN", "MAX"))
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="arrivals per decode step (Poisson)")
    ap.add_argument("--policy", default=None,
                    choices=["nominal", "static", "power-cap",
                             "energy-aware"])
    ap.add_argument("--governor", action="store_true",
                    help="deprecated: same as --policy energy-aware")
    ap.add_argument("--slowdown-budget", type=float, default=0.0)
    ap.add_argument("--freq-mhz", type=int, default=None)
    ap.add_argument("--power-cap-w", type=float, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if cfg.family not in SLOT_FAMILIES:
        ap.error(f"--arch {args.arch}: this launcher serves the slot "
                 f"families {SLOT_FAMILIES}, not {cfg.family!r}; "
                 f"ServeEngine.generate serves the others")
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)

    # explicit --policy wins; --governor is the deprecated alias (same
    # precedence as TrainConfig.resolved_policy)
    policy = args.policy or ("energy-aware" if args.governor else "nominal")
    session = EnergySession(policy=policy,
                            slowdown_budget=args.slowdown_budget,
                            freq_mhz=args.freq_mhz,
                            cap_w=args.power_cap_w)
    _, reqs, rep = run(
        cfg, requests=args.requests, slots=args.slots, max_len=args.max_len,
        prompt_lens=tuple(args.prompt_lens), new_tokens=args.new_tokens,
        rate=args.rate, temperature=args.temperature,
        session=session)
    for i, o in enumerate(rep.outputs[:4]):
        print(f"req{i} (prompt {rep.requests.prompt_len[i]}): {o.tolist()}")
    print(f"{len(reqs)} requests  {rep.tokens_out} tokens  "
          f"{rep.n_steps} decode steps  occupancy {rep.occupancy_mean:.2f}"
          f"  wall {rep.wall_s:.2f} s")
    if rep.n_steps:
        read = rep.ticks.kv_read_positions.sum()
        print(f"decode attention read {read} kv positions, "
              f"{100 * rep.ticks.kv_positions.sum() / read:.1f}% of them "
              f"live")
    for name, ms in latencies_ms(rep).items():
        if ms.size:
            p50, p95 = np.percentile(ms, [50, 95])
            print(f"{name:<10} p50 {p50:9.1f} ms  p95 {p95:9.1f} ms")
    line = slowest_tick(rep)
    if line:
        print(line)
    s = session.summary()
    print(f"policy {s['policy']}  energy {s['energy_j']:.1f} J  "
          f"savings {s['savings_pct']:.1f}%  "
          f"mode-hours {s['mode_hours_pct']}")


if __name__ == "__main__":
    main()
