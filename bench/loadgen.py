"""The traffic generator's common parts: what a window's client saw
(``WindowLog``), the arrival schedules of open loops, and the host spans of
a traced window. The loops themselves are ``loops/<name>.py``, chosen by a
mix file's ``"loop"`` key and found by name; everything else a loop needs
is in the mix file:

- ``"loop": "closed"`` (MLPerf Inference's Offline and SingleStream
  scenarios): ``backlog`` requests stay outstanding; each time ``refill``
  of them have completed, as many new ones go in. Both count in ``unit``:
  ``"batch"`` (the session's largest bucket) or ``"request"``.
- ``"loop": "open"`` (MLPerf's Server scenario): single-image requests sent
  on schedule whatever the server does, at ``rate_per_s``; ``arrivals``
  chooses the process (``loadgen.ARRIVALS``), Poisson by default.

A mix may also name the session's ``buckets`` (``"full"``, the program's
default ``"powers_of_two"``, or a list whose largest is the session's
``max_batch``), extra ``serve`` keywords (such as ``queue_limit`` and
``on_overload``), and the ``expected_errors`` (exception class names, such
as ``"Overloaded"``) that count as shed rather than failed. Every loop
cycles through a seeded pool of ``pool`` distinct inputs.

A request is timed from when it was due (open loop) or sent (closed loop)
to when its future resolved. The log keeps no future and no copy of an
answer that it has already seen for the same input, so what it holds stays
small however long the window. In a traced window the loops record what
they were doing (``bench.sleep``, ``bench.submit``, ``bench.wait``) as host
spans.
"""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

PENDING, OK, FAILED, SHED = 0, 1, 2, 3


class WindowLog:
    """What the client saw in one window (monotonic seconds). ``answers``
    maps each distinct ``(pool index, logits bytes)`` to how many requests
    got it."""

    def __init__(self, t_start: float, seconds: float,
                 expected_errors=()):
        self.t_start, self.t_end = t_start, t_start + seconds
        self.expected = tuple(expected_errors)
        self.due, self.sent, self.pool_idx = [], [], []
        self.done: list = []        # resolve time, None until resolved
        self.status: list = []
        self.answers: dict = {}
        self.resolved = 0
        self._cond = threading.Condition()

    def track(self, fut, due: float, sent: float, idx: int) -> None:
        i = len(self.due)
        self.due.append(due)
        self.sent.append(sent)
        self.pool_idx.append(idx)
        self.done.append(None)
        self.status.append(PENDING)
        fut.add_done_callback(lambda f, i=i: self._resolve(f, i))

    def _resolve(self, fut, i: int) -> None:
        t = time.monotonic()
        exc = None if fut.cancelled() else fut.exception()
        key = None
        if fut.cancelled() or exc is not None:
            status = (SHED if exc is not None
                      and type(exc).__name__ in self.expected else FAILED)
        else:
            status = OK
            y = np.asarray(fut.result(), np.float32).reshape(-1)
            key = (self.pool_idx[i], y.tobytes())
        with self._cond:
            if key is not None:
                self.answers[key] = self.answers.get(key, 0) + 1
            self.done[i], self.status[i] = t, status
            self.resolved += 1
            self._cond.notify_all()

    def wait_resolved(self, n: int, timeout_s: float) -> bool:
        """Wait until ``n`` requests have resolved, at most ``timeout_s``."""
        with self._cond:
            return self._cond.wait_for(lambda: self.resolved >= n, timeout_s)

    def settle(self, timeout_s: float = 60.0) -> None:
        """Wait for every request, at most ``timeout_s`` past the window."""
        limit = max(self.t_end, time.monotonic()) + timeout_s
        self.wait_resolved(len(self.due), max(0.0, limit - time.monotonic()))

    def counts(self) -> dict:
        s = np.asarray(self.status, np.int8)
        return {"answered": int((s == OK).sum()), "shed": int((s == SHED).sum()),
                "failed": int(((s == FAILED) | (s == PENDING)).sum())}

    def latencies_ms(self) -> np.ndarray:
        """Per request, due time to result; ``inf`` where it was not
        answered."""
        ok = np.asarray(self.status) == OK
        done = np.array([d if d is not None else np.inf for d in self.done])
        return np.where(ok, done - np.asarray(self.due), np.inf) * 1e3

    def answered_by(self, t: float) -> int:
        ok = np.asarray(self.status) == OK
        done = np.array([d if d is not None else np.inf for d in self.done])
        return int(np.sum(ok & (done <= t)))


# -- arrival schedules: offsets from the window's start, in seconds ---------

def _exp_quantiles(n: int, mean: float, rng) -> np.ndarray:
    """``n`` exponential values of mean ``mean``: the distribution's
    midpoint quantiles, in an order drawn from ``rng``."""
    q = (np.arange(n) + 0.5) / n
    v = -np.log1p(-q) * mean
    rng.shuffle(v)
    return v


def poisson(mix: dict, rate: float, seconds: float, rng) -> np.ndarray:
    """Exponential gaps of mean ``1/rate``, as many as fit ``seconds``."""
    return np.cumsum(_exp_quantiles(int(rate * seconds), 1.0 / rate, rng))


def mmpp(mix: dict, rate: float, seconds: float, rng) -> np.ndarray:
    """A two-state Markov-modulated Poisson process: base phases at ``rate``
    and burst phases at ``rate * burst_factor``, alternating, with
    exponential dwell times of mean ``base_dwell_s`` and ``burst_dwell_s``.
    The dwells are the exponential's quantiles, scaled so that the phases
    fill ``seconds`` exactly, and each phase's arrivals its own Poisson
    quantiles: every seed gets the same phases and the same number of
    requests, in its own order."""
    a = mix["arrivals"]
    base_s, burst_s = a["base_dwell_s"], a["burst_dwell_s"]
    k = max(1, round(seconds / (base_s + burst_s)))
    dwell = [_exp_quantiles(k, base_s, rng), _exp_quantiles(k, burst_s, rng)]
    scale = seconds / (dwell[0].sum() + dwell[1].sum())
    rates = (rate, rate * a["burst_factor"])
    t, out = 0.0, []
    for j in range(k):
        for state in (0, 1):
            d = dwell[state][j] * scale
            n = round(rates[state] * d)
            if n:
                gaps = _exp_quantiles(n, d / n, rng)
                out.append(t + np.cumsum(gaps) * (d / gaps.sum()))
            t += d
    return np.concatenate(out) if out else np.zeros(0)


ARRIVALS = {"poisson": poisson, "mmpp": mmpp}


def arrival_times(mix: dict, rate: float, seconds: float,
                  seed: int) -> np.ndarray:
    """The due offsets of the mix's arrival process, from ``seed``."""
    process = mix.get("arrivals", {}).get("process", "poisson")
    rng = np.random.default_rng(seed % 2 ** 64)
    return ARRIVALS[process](mix, rate, seconds, rng)


@contextlib.contextmanager
def span(spans: list | None, name: str):
    """Append ``[name, start_ns, duration_ns]`` (``time.time_ns``) to
    ``spans`` around the block, where a trace is being taken."""
    if spans is None:
        yield
        return
    t = time.time_ns()
    try:
        yield
    finally:
        spans.append([name, t, time.time_ns() - t])
