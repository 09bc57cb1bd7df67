"""An engine with no model: ``serve()`` drives it as it drives a
``ContinuousEngine``, and a clock that moves one unit per decode step (plus
an optional cost per prefilled token) stands in for the chip."""
from dataclasses import dataclass

import numpy as np


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


@dataclass
class _Prefix:
    token: int
    length: int
    max_new: int
    temperature: float = 0.0
    state: object = None


class FakeEngine:
    def __init__(self, clock: Clock, max_slots: int = 16,
                 max_len: int = 2048, prefill_per_token: float = 0.0):
        self.clock, self.max_slots, self.max_len = clock, max_slots, max_len
        self.prefill_per_token = prefill_per_token
        self.n_prefills = 0
        self.session = None

    def prefill(self, request, temperature=0.0):
        L = max(len(request.prompt), 1)
        self.clock.t += self.prefill_per_token * L
        self.n_prefills += 1
        return _Prefix(token=0, length=L,
                       max_new=max(1, min(request.max_new_tokens,
                                          self.max_len - L)))

    def insert(self, prefix, slot):
        pass

    def generate_step(self, active=None):
        self.clock.t += 1.0
        return np.zeros(self.max_slots, np.int32)

    def observe(self, *a, **k):
        return None
