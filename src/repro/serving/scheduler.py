"""Continuous-batching scheduler: an open-loop request queue over a
:class:`~repro.serving.ContinuousEngine` slot pool.

Every tick admits arrived requests into free slots (prefill + insert), runs
one ``generate_step`` across the pool, and evicts finished sequences —
freed slots are refilled on the very next tick, so the pool stays full
under load with no lock-step barrier. Time is counted in *decode steps*,
not wall-clock: arrival processes expressed in step units make scheduling
decisions (and tests) machine-independent.

What a run did is recorded as it happens, on the host clock
(``time.perf_counter``), and as spans of the profiler's trace
(``jax.profiler.TraceAnnotation``, under a microsecond each when no trace
is being collected):

- ``serve.tick``: a loop turn that steps the pool, from its admissions
  through its bookkeeping (where the pool stood idle, the admissions that
  woke it precede the span);
- ``serve.admit``: one admission, ``prefill`` + ``insert``;
- ``serve.step``: the dispatch of ``generate_step``;
- ``serve.read``: the wait for the step's tokens on the host;
- ``serve.book``: the token appends, the evictions and ``engine.observe``.

:class:`ServeReport` holds one :class:`TickRecords` entry per decode step
(the k-th ``serve.tick`` span of a trace is its k-th entry) and one
:class:`RequestRecords` entry per request. A request's time to first token
is ``ticks.read_s[first_tick] - due_s``, and the gaps between its tokens
are ``np.diff(ticks.read_s[first_tick:last_tick + 1])``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation


def poisson_arrivals(n: int, rate_per_step: float, seed: int = 0
                     ) -> np.ndarray:
    """Open-loop Poisson arrival times in decode-step units: cumulative sum
    of exponential inter-arrival gaps at ``rate_per_step`` requests/step."""
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_per_step, size=n))


@dataclass
class TickRecords:
    """One entry per decode step, in order; times in seconds on
    ``time.perf_counter``. A tick carries the admissions made since the
    previous step's tokens reached the host."""
    start_s: np.ndarray         # the loop's first turn after the previous read
    read_s: np.ndarray          # the step's tokens on the host
    admit_s: np.ndarray         # seconds in its serve.admit spans
    step_s: np.ndarray          # seconds in serve.step (the dispatch)
    wait_s: np.ndarray          # seconds in serve.read (waiting for tokens)
    admitted: np.ndarray        # requests admitted
    prompt_tokens: np.ndarray   # their prompt tokens (no page padding)
    active: np.ndarray          # slots stepped
    kv_positions: np.ndarray    # positions the stepped slots attend, summed
    kv_read_positions: np.ndarray   # positions the step's attention read

    @classmethod
    def from_rows(cls, rows: list) -> "TickRecords":
        cols = np.asarray(rows, float).reshape(len(rows), 10).T
        return cls(*cols[:5], *cols[5:].astype(np.int64))


@dataclass
class RequestRecords:
    """One entry per request, in input order; times as :class:`TickRecords`.
    A request done at prefill has no tick (``first_tick == last_tick ==
    -1``)."""
    due_s: np.ndarray           # the step counter reached its arrival step
    admit_s: np.ndarray         # its prefill began
    first_tick: np.ndarray      # the first decode step that stepped it
    last_tick: np.ndarray       # the last
    prompt_len: np.ndarray      # prompt tokens prefilled
    page: np.ndarray            # positions its prefill computed (0: unknown)
    attn_blocks: np.ndarray     # (q, kv) block pairs its prefill attention
    #                             visited as the flash kernel (0: it ran as
    #                             chunked_attention, every pair of the page)


@dataclass
class ServeReport:
    """What a :func:`serve` run did: per-request outputs, the tick and
    request records, and the counts the bench contract is scored on."""
    outputs: List[np.ndarray]          # per request, [max_new] int32
    n_prefills: int
    wall_s: float
    tokens_out: int                    # generated tokens actually requested
    queue_peak: int                    # max requests waiting for a slot
    ticks: TickRecords
    requests: RequestRecords
    session: Optional[object] = None   # the engine's EnergySession, if any

    @property
    def n_steps(self) -> int:
        """Decode steps executed."""
        return len(self.ticks.read_s)

    @property
    def occupancy_mean(self) -> float:
        """Mean occupied slots per decode step (0 without one)."""
        return float(self.ticks.active.mean()) if self.n_steps else 0.0


def _kv_read(pos: np.ndarray, max_len: int, chunk: int) -> int:
    """Positions a decode step's attention reads with every slot at
    ``pos``: each slot's extent rounded up to ``chunk``, or with ``chunk``
    0 all ``max_len`` positions of every slot."""
    if not chunk:
        return len(pos) * max_len
    extent = np.minimum(pos + 1, max_len)
    return int((-(-extent // chunk)).sum()) * chunk


def _attn_blocks(length: int, page: int, blocks: Tuple[int, int]) -> int:
    """(q, kv) block pairs a prefill's attention visited: the flash
    kernel's count where both ``blocks`` divide the page, else 0."""
    bq, bk = blocks
    if not bq or not page or page % bq or page % bk:
        return 0
    from repro.kernels.flash_attention import visited_blocks
    return visited_blocks(length, page, bq, bk)


def _span(name: str) -> TraceAnnotation:
    span = TraceAnnotation(name)
    span.__enter__()
    return span


def serve(engine, requests: Sequence, arrivals: Optional[Sequence] = None,
          temperature: float = 0.0) -> ServeReport:
    """Serve ``requests`` through the engine's slot pool to completion.

    ``arrivals`` gives each request's arrival time in decode-step units
    (default: everything queued at t=0). Each tick: admit as many arrived
    requests as there are free slots, step the pool once, evict finished
    sequences. With an :class:`~repro.power.EnergySession` on the engine,
    each tick reports its prefills and decode step as distinct roofline
    profiles — the per-phase power-policy hook.
    """
    n = len(requests)
    arr = (np.zeros(n) if arrivals is None
           else np.asarray(arrivals, dtype=float))
    if len(arr) != n:
        raise ValueError(f"{len(arr)} arrival times for {n} requests")
    order = np.argsort(arr, kind="stable")
    arr_sorted = arr[order]

    S = engine.max_slots
    # an engine without ``kv_chunk`` reads every position of every slot,
    # one without ``prefill_blocks`` prefills through chunked_attention
    chunk = getattr(engine, "kv_chunk", 0)
    blocks = getattr(engine, "prefill_blocks", (0, 0))
    outputs: List[Optional[np.ndarray]] = [None] * n
    partial: List[Optional[List[int]]] = [None] * n
    slot_req = [-1] * S                 # request index occupying each slot
    slot_left = np.zeros(S, np.int64)   # tokens still to generate per slot
    slot_pos = np.zeros(S, np.int64)    # the engine's position of each slot
    active = np.zeros(S, bool)
    free = list(range(S))[::-1]
    qi = 0                              # next arrival (in sorted order)
    done = 0
    step = 0
    queue_peak = 0
    rows: list = []                     # TickRecords, one tuple per tick
    admit_t = np.zeros(n)
    first_tick = np.full(n, -1, np.int64)
    last_tick = np.full(n, -1, np.int64)
    prompt_len = np.zeros(n, np.int64)
    page = np.zeros(n, np.int64)
    attn_blocks = np.zeros(n, np.int64)
    t0 = time.perf_counter()
    reached, reached_t = [0], [t0]      # step counter values, when reached
    begun = None                        # the tick under way began
    n_adm = adm_tokens = 0
    adm_s = 0.0
    while done < n:
        tick_t0 = time.perf_counter()
        if begun is None:
            begun = tick_t0
        tick = _span("serve.tick") if active.any() else None
        n_pre = 0
        while free and qi < n and arr_sorted[qi] <= step:
            i = int(order[qi])
            qi += 1
            slot = free.pop()
            with TraceAnnotation("serve.admit"):
                a0 = time.perf_counter()
                pf = engine.prefill(requests[i], temperature)
                engine.insert(pf, slot)
                adm_s += time.perf_counter() - a0
            slot_pos[slot] = pf.length
            if step > reached[-1]:      # the pool stood idle: the counter
                reached.append(step)    # jumped to this arrival
                reached_t.append(a0)
            admit_t[i] = a0
            prompt_len[i] = pf.length
            page[i] = getattr(pf, "page", 0)
            attn_blocks[i] = _attn_blocks(pf.length, page[i], blocks)
            n_adm += 1
            adm_tokens += pf.length
            # keep the first token as a device scalar: forcing it here would
            # serialize every admission on its own B=1 prefill; it is
            # materialized at eviction, when the value is long since ready
            partial[i] = [pf.token]
            n_pre += 1
            if pf.max_new <= 1:         # done at prefill: slot never decodes
                outputs[i] = np.asarray([int(v) for v in partial[i]],
                                        np.int32)
                done += 1
                free.append(slot)
            else:
                slot_req[slot] = i
                slot_left[slot] = pf.max_new - 1
                active[slot] = True
                first_tick[i] = len(rows)
        arrived = int(np.searchsorted(arr_sorted, step, side="right"))
        queue_peak = max(queue_peak, arrived - qi)
        if not active.any():
            if n_pre:
                engine.observe(n_pre, 0,
                               wall_s=time.perf_counter() - tick_t0)
            if done < n and qi < n:
                # pool idle until the next arrival: skip the dead time
                step = max(step + 1, int(np.ceil(arr_sorted[qi])))
            continue
        if tick is None:                # woken from idle by its admissions
            tick = _span("serve.tick")
        with TraceAnnotation("serve.step"):
            s0 = time.perf_counter()
            pending = engine.generate_step(active)
            s1 = time.perf_counter()
        with TraceAnnotation("serve.read"):
            toks = np.asarray(pending)
            t_read = time.perf_counter()
        with TraceAnnotation("serve.book"):
            stepped = np.flatnonzero(active)
            # a slot at position p writes p and attends p + 1 positions
            kv = int(slot_pos[stepped].sum()) + len(stepped)
            read = _kv_read(slot_pos, engine.max_len, chunk)
            slot_pos[stepped] += 1
            rows.append((begun, t_read, adm_s, s1 - s0, t_read - s1, n_adm,
                         adm_tokens, len(stepped), kv, read))
            step += 1
            reached.append(step)
            reached_t.append(t_read)
            begun, n_adm, adm_tokens, adm_s = None, 0, 0, 0.0
            for s in stepped:
                i = slot_req[s]
                partial[i].append(int(toks[s]))
                slot_left[s] -= 1
                if slot_left[s] == 0:
                    active[s] = False
                    slot_req[s] = -1
                    free.append(int(s))
                    outputs[i] = np.asarray([int(v) for v in partial[i]],
                                            np.int32)
                    last_tick[i] = len(rows) - 1
                    done += 1
            engine.observe(n_pre, 1,
                           wall_s=time.perf_counter() - tick_t0)
        tick.__exit__(None, None, None)
    wall_s = time.perf_counter() - t0
    # due: the counter's first value at or past the arrival step
    due = np.asarray(reached_t)[np.searchsorted(reached, arr, side="left")]
    return ServeReport(
        outputs=outputs, n_prefills=engine.n_prefills, wall_s=wall_s,
        tokens_out=int(sum(len(o) for o in outputs)), queue_peak=queue_peak,
        ticks=TickRecords.from_rows(rows),
        requests=RequestRecords(due_s=due, admit_s=admit_t,
                                first_tick=first_tick, last_tick=last_tick,
                                prompt_len=prompt_len, page=page,
                                attn_blocks=attn_blocks),
        session=getattr(engine, "session", None))
