"""The load generator: one general generator for every traffic mix.

A mix is a data file, `bench/traffic/<mix>.json`, with these keys:

  loop     "closed": `clients` callers, each sending its next request the
           moment the previous one completes (no think time);
           "open": requests fall due at Poisson arrivals of `rate` requests
           per second, whether or not earlier ones have completed.
  rows     query rows per request.
  k        results per query row.
  clients  (closed) number of callers.
  rate     (open) offered requests per second.

Every request takes the next `rows` rows of the query pool, in an order drawn
from the seed.  A closed-loop client resubmits from the completion callback
of its previous request, so the load needs no threads of its own and a
completed batch's callers are all queued again before the server looks for
its next batch.  The open loop runs one generator thread that sleeps until
each request falls due.  Latency is timed from `due`: the submit time in the
closed loop, the scheduled arrival in the open loop, so a stall that delays
later arrivals counts against them.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import numpy as np

LOOPS = ("closed", "open")


@dataclasses.dataclass
class Record:
    """One request: its pool rows, when it fell due, and what came back."""

    index: int
    rows: np.ndarray              # indices into the query pool
    due: float                    # perf_counter the request fell due
    submitted: float = 0.0        # perf_counter the submit call began
    done: Optional[float] = None  # perf_counter of completion
    result: object = None         # what the future resolved to
    error: Optional[BaseException] = None

    @property
    def latency(self) -> float:
        return self.done - self.due


def validate(traffic: dict) -> dict:
    """Check a traffic mix's parameters; returns it unchanged."""
    loop = traffic.get("loop")
    if loop not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}, got {loop!r}")
    need = ("rows", "k") + (("clients",) if loop == "closed" else ("rate",))
    for key in need:
        if key not in traffic or not traffic[key] > 0:
            raise ValueError(f"{loop}-loop traffic needs a positive {key!r}")
    return traffic


def poisson_due_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Seconds after the window opens at which open-loop requests fall due.

    rate * seconds arrivals whose gaps are the quantiles of the exponential
    distribution of mean 1 / rate, in an order drawn from the seed: every
    seed offers the same number of requests and the same set of gaps, so a
    seed changes the order of the work and never its amount."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds} s offers no request")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    np.random.default_rng(seed).shuffle(gaps)
    return np.cumsum(gaps)


def pool_order(pool_rows: int, seed: int) -> np.ndarray:
    """The order in which requests take rows of the query pool."""
    return np.random.default_rng(seed).permutation(pool_rows)


class Load:
    """Drive `submit(record) -> Future` with one traffic mix over a window.

    `start(t0, t_end)` begins the window; `join(grace)` returns once the
    window has closed and every request submitted in it has completed, or
    `grace` seconds after the close, whichever is first."""

    def __init__(self, traffic: dict, submit: Callable, pool_rows: int,
                 seed: int, seconds: float,
                 clock: Callable[[], float] = time.perf_counter):
        self.traffic = validate(traffic)
        self.submit = submit
        self.clock = clock
        self.rows = int(traffic["rows"])
        self.order = pool_order(pool_rows, seed)
        self.offsets = (poisson_due_offsets(float(traffic["rate"]), seconds, seed)
                        if traffic["loop"] == "open" else None)
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._outstanding = 0
        self._t_end = 0.0
        self._thread: Optional[threading.Thread] = None

    # -- requests ----------------------------------------------------------
    def _new_record(self, due: float) -> Record:
        with self._lock:
            i = len(self.records)
            lo = (i * self.rows) % self.order.size
            rows = np.take(self.order, np.arange(lo, lo + self.rows), mode="wrap")
            rec = Record(index=i, rows=rows, due=due)
            self.records.append(rec)
            self._outstanding += 1
        return rec

    def _finish(self) -> None:
        with self._lock:
            self._outstanding -= 1
            self._idle.notify_all()

    def _issue(self, due: float, closed: bool) -> None:
        rec = self._new_record(due)
        rec.submitted = self.clock()
        try:
            fut = self.submit(rec)
        except Exception as e:  # a refused submit is a failed request
            rec.error = e
            self._finish()
            return
        fut.add_done_callback(lambda f: self._on_done(rec, f, closed))

    def _on_done(self, rec: Record, fut, closed: bool) -> None:
        rec.done = self.clock()
        try:
            rec.result = fut.result()
        except Exception as e:
            rec.error = e
        if closed and rec.done < self._t_end:
            self._issue(self.clock(), closed=True)
        self._finish()

    # -- the window ----------------------------------------------------------
    def start(self, t0: float, t_end: float) -> None:
        self._t_end = t_end
        if self.traffic["loop"] == "closed":
            for _ in range(int(self.traffic["clients"])):
                self._issue(self.clock(), closed=True)
            return
        self._thread = threading.Thread(target=self._generate, args=(t0,),
                                        name="bench-open-loop", daemon=True)
        self._thread.start()

    def _generate(self, t0: float) -> None:
        for off in self.offsets:
            due = t0 + float(off)
            wait = due - self.clock()
            if wait > 0:
                time.sleep(wait)
            self._issue(due, closed=False)

    def join(self, grace: float) -> bool:
        """Wait for the close and the requests in flight; True when all came."""
        deadline = self._t_end + grace
        if self._thread is not None:
            self._thread.join(max(0.0, deadline - self.clock()))
        with self._lock:
            while self._outstanding > 0 or self.clock() < self._t_end:
                left = deadline - self.clock()
                if left <= 0:
                    break
                self._idle.wait(min(left, 0.05))
            return self._outstanding == 0

    def lateness(self) -> np.ndarray:
        """Seconds each request was submitted after it fell due."""
        return np.array([r.submitted - r.due for r in self.records])
