"""Closed loop: a batch evaluation over a problem set.

Keeps ``slots / n_samples`` requests decoding plus ``backlog`` waiting;
each time a request completes, the client sends the next one.  A request
is due when it is sent.

A loop module gives ``Loop(mix, generator, n_slots)`` with ``start`` and
``after_step``; both get the scheduler, ``submit(item, due)`` and the
time now on the harness's clock.
"""
from __future__ import annotations


class Loop:
    def __init__(self, mix: dict, gen, n_slots: int):
        self.gen = gen
        self.target = n_slots // mix["n_samples"] + mix["backlog"]
        self.sent = 0
        self.base = 0

    def _top_up(self, sched, submit, now: float) -> None:
        completed = sched.metrics.completed_requests - self.base
        while self.sent - completed < self.target:
            submit(self.gen.next(), now)
            self.sent += 1

    def start(self, sched, submit, now: float) -> None:
        self.base = sched.metrics.completed_requests  # warm-up's requests
        self._top_up(sched, submit, now)

    def after_step(self, sched, submit, now: float) -> None:
        self._top_up(sched, submit, now)
