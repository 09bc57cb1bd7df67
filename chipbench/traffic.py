"""The one traffic generator: a mix file in ``traffic/`` -> requests.

A mix names distributions of prompt and output lengths and an arrival
process in the scheduler's own clock (decode steps). The lengths and the
arrival times are one fixed trace, drawn from streams of their own that
no seed touches, so that every seed serves the same requests at the same
steps and a run's spread is the system's, not the draw's: the pool is a
queue at 0.8 of its capacity, where the order of long and short requests
alone moves the 95th percentile of the wait by half (PERF.md). A shorter
window serves a prefix of a longer one's trace. The seed draws the prompts'
tokens (and, elsewhere, the weights and the sample that is compared).

A mix's ``window`` sizes a run in decode steps: ``fill_steps`` before the
measured window, so that the pool holds its steady load when it opens, and
``steps_per_s`` (decode steps a second of the mix's cell on the chip)
times ``--seconds`` in it. Requests arrive until the window closes.

Adapted from the program's ``serving.poisson_arrivals`` and
``launch/serve.make_requests`` (log-uniform lengths, exponential gaps).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

#: the fixed streams of the trace (never the run's seed)
TRACE_SEED = 20240801


@dataclass
class Traffic:
    prompt_lens: np.ndarray     # [n] int
    output_lens: np.ndarray     # [n] int
    arrivals: np.ndarray        # [n] float, decode steps, ascending
    prompts: List[np.ndarray]   # [n] int32 token ids


def _stream(which: int) -> np.random.Generator:
    return np.random.default_rng([TRACE_SEED, which])


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "log_uniform":
        # inclusive of hi, as launch/serve.make_requests draws them
        x = np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n))
        return np.clip(x.astype(np.int64), lo, hi)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps in decode steps. ``gamma`` with ``shape`` 1 is
    Poisson; a shape under 1 gives bursts (coefficient of variation
    1/sqrt(shape)), as BurstGPT's Gamma-distributed gaps."""
    if spec["process"] != "gamma":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    k = float(spec["shape"])
    mean = 1.0 / float(spec["rate_per_step"])
    return np.asarray([rng.gamma(k, mean / k) for _ in range(n)])


def window(mix: dict, seconds: float) -> Tuple[int, int]:
    """The decode steps at which the measured window opens and closes."""
    w = mix["window"]
    fill = int(w["fill_steps"])
    return fill, fill + int(math.ceil(seconds * float(w["steps_per_s"])))


def arriving_before(mix: dict, horizon: float) -> int:
    """How many of the trace's requests arrive before step ``horizon``."""
    n = 64
    while True:
        gaps = _gaps(mix["arrivals"], n, _stream(2))
        arrivals = np.concatenate([[0.0], np.cumsum(gaps[1:])])
        if arrivals[-1] >= horizon:
            return int(np.searchsorted(arrivals, horizon, side="left"))
        n *= 2


def max_len(mix: dict) -> int:
    """Cache positions a slot needs for the mix's longest request, to a
    multiple of 128 (the TPU's lane width)."""
    need = int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
    return -(-need // 128) * 128


def generate(mix: dict, n: int, seed: int, vocab: int) -> Traffic:
    """The first ``n`` requests of ``mix``'s trace, with prompt tokens drawn
    from ``seed``."""
    prompt = _lengths(mix["prompt_tokens"], n, _stream(0))
    output = _lengths(mix["output_tokens"], n, _stream(1))
    gaps = _gaps(mix["arrivals"], n, _stream(2))
    # the first request is due at once: the window opens on an arrival
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[1:])])
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, int(L), dtype=np.int32) for L in prompt]
    return Traffic(prompt, output, arrivals, prompts)
