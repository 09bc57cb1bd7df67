"""The comparison that decides ``correct`` for a served model.

After the window closes, a sample of the finished requests, drawn from the
seed and always holding the longest one, is packed into one row: each
request's prompt followed by the tokens it was served. The plain reference
runs once over that row, and for every served token reads the gap by which
the token's reference logit lies below the reference's best logit at that
position. Greedy decoding serves the argmax, so a sound program's gaps are
rounding: bf16 against float32, where two logits nearly tie. A wrong
position, mask, cache row or slot serves tokens far down the reference's
ranking.

The number compared is the widest gap over the sample, against the limit in
the configuration's file (``check.max_logit_gap``). :func:`gaps` also gives
the control's: the reference itself in fp8, which at each position picks
its own argmax, read by the float32 reference's logits in the same way.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


@dataclass
class Number:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


@dataclass
class Packed:
    tokens: np.ndarray      # [T] int32
    pos: np.ndarray         # [T] position inside its request
    seg: np.ndarray         # [T] which request (padding: -1)
    rows: np.ndarray        # [R] rows whose next token was served
    served: np.ndarray      # [R] the served tokens


def sample(seed: int, requests, outputs, budget: int) -> List[int]:
    """Finished requests to compare: the longest, then others in an order
    drawn from the seed, while prompt and served tokens fit ``budget``."""
    done = [i for i, o in enumerate(outputs) if o is not None and len(o)]
    size = {i: len(requests[i].prompt) + len(outputs[i]) - 1 for i in done}
    longest = max(done, key=lambda i: (size[i], -i))
    rest = [i for i in np.random.default_rng(seed).permutation(done)
            if i != longest]
    chosen, used = [longest], size[longest]
    for i in rest:
        if used + size[int(i)] <= budget:
            chosen.append(int(i))
            used += size[int(i)]
    return chosen


def pack(requests, outputs, chosen: Sequence[int], budget: int) -> Packed:
    toks, pos, seg, rows, served = [], [], [], [], []
    at = 0
    for j, i in enumerate(chosen):
        p = np.asarray(requests[i].prompt, np.int32)
        o = np.asarray(outputs[i], np.int32)
        row = np.concatenate([p, o[:-1]])
        toks.append(row)
        pos.append(np.arange(len(row)))
        seg.append(np.full(len(row), j))
        rows.append(at + len(p) - 1 + np.arange(len(o)))
        served.append(o)
        at += len(row)
    T = -(-max(budget, at) // 512) * 512
    pad = T - at
    return Packed(
        tokens=np.concatenate(toks + [np.zeros(pad, np.int32)]),
        pos=np.concatenate(pos + [np.zeros(pad, np.int64)]).astype(np.int32),
        seg=np.concatenate(seg + [np.full(pad, -1)]).astype(np.int32),
        rows=np.concatenate(rows), served=np.concatenate(served))


def gaps(ref, params, conf: dict, pk: Packed, control: bool = False):
    """Gap of every served token; with ``control``, also the gap of the
    fp8 reference's own choice at the same positions."""
    import jax.numpy as jnp
    dev = {k: jnp.asarray(getattr(pk, k)) for k in ("tokens", "pos", "seg")}
    targets = np.zeros(len(pk.tokens), np.int32)
    targets[pk.rows] = pk.served
    x = ref.hidden(params, conf, dev["tokens"], dev["pos"], dev["seg"])
    best, at, _ = (np.asarray(a) for a in ref.logits_at(
        params, conf, x, jnp.asarray(targets)))
    served_gap = (best - at)[pk.rows]
    if not control:
        return served_gap, None
    x8 = ref.hidden(params, conf, dev["tokens"], dev["pos"], dev["seg"],
                    fp8=True)
    _, _, pick = ref.logits_at(params, conf, x8, jnp.asarray(targets),
                               fp8=True)
    _, at8, _ = (np.asarray(a) for a in ref.logits_at(params, conf, x, pick))
    return served_gap, (best - at8)[pk.rows]


def check_served(cell, params, requests, outputs):
    """The numbers compared, and what they were read over."""
    budget = int(cell.mix["check"]["packed_tokens"])
    chosen = sample(cell.seed, requests, outputs, budget)
    pk = pack(requests, outputs, chosen, budget)
    control = bool(getattr(cell, "control", False))
    served_gap, control_gap = gaps(cell.family.reference, params, cell.conf,
                                   pk, control=control)
    checks = [Number("max_logit_gap", float(np.max(served_gap)),
                     float(cell.conf["check"]["max_logit_gap"]))]
    info = {"requests_compared": len(chosen),
            "tokens_compared": int(len(served_gap))}
    if control:
        info["control_max_logit_gap"] = float(np.max(control_gap))
    return checks, info
