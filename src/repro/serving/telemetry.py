"""Timing of a serving session over its whole life, and its host spans.

* :class:`LogHistogram` — counts of durations in log-spaced bins, 16 to the
  octave (each bin 2^(1/16), about 4.4%, wider than the last) from 1 µs to
  2^32 µs (71 minutes), with one bin below and one above. It keeps every
  sample of a long-lived session in a fixed 514 counts, a percentile reads
  to within a bin, and two copies taken apart in time difference to the
  histogram of the window between them.
* :class:`SpanRecorder` — an in-memory list of ``(name, start_ns,
  duration_ns, ref)`` on the ``time.time_ns`` clock, the clock a profiler
  trace's ``profile_start_time`` is read on, so a caller can lay the spans
  over a device trace. ``ServingSession.record_spans()`` turns one on.
"""
from __future__ import annotations

import bisect
import math
import time

BINS_PER_OCTAVE = 16
OCTAVES = 32
LOWEST_MS = 1e-3
N_BINS = BINS_PER_OCTAVE * OCTAVES + 2


def _bin(ms: float) -> int:
    if ms < LOWEST_MS:
        return 0
    return min(N_BINS - 1,
               int(BINS_PER_OCTAVE * math.log2(ms / LOWEST_MS)) + 1)


def _center_ms(i: int) -> float:
    """The geometric middle of bin ``i`` (the edge for the two open bins)."""
    if i == 0:
        return LOWEST_MS / 2
    if i == N_BINS - 1:
        return LOWEST_MS * 2.0 ** OCTAVES
    return LOWEST_MS * 2.0 ** ((i - 0.5) / BINS_PER_OCTAVE)


class LogHistogram:
    """Durations in milliseconds, counted in log-spaced bins. Not
    thread-safe: the owner serializes ``add`` against reads."""

    __slots__ = ("counts",)

    def __init__(self, counts: list[int] | None = None):
        self.counts = [0] * N_BINS if counts is None else counts

    def add(self, ms: float) -> None:
        self.counts[_bin(ms)] += 1

    @property
    def count(self) -> int:
        return sum(self.counts)

    def percentile(self, q: float) -> float:
        """The ``q`` quantile (0..1) in ms, interpolated between the ranks
        around ``q * (count - 1)`` as ``numpy.percentile`` does, each rank
        read at its bin's middle; 0.0 when empty."""
        n = self.count
        if not n:
            return 0.0
        cum, total = [], 0
        for c in self.counts:
            total += c
            cum.append(total)
        rank = q * (n - 1)
        lo = math.floor(rank)
        at = [_center_ms(bisect.bisect_right(cum, r)) for r in (lo, lo + 1)]
        if lo + 1 >= n:
            return at[0]
        return at[0] + (rank - lo) * (at[1] - at[0])

    def copy(self) -> "LogHistogram":
        return LogHistogram(list(self.counts))

    def __sub__(self, earlier: "LogHistogram") -> "LogHistogram":
        return LogHistogram([a - b for a, b in zip(self.counts,
                                                   earlier.counts)])

    def __repr__(self) -> str:
        return (f"LogHistogram(count={self.count}, "
                f"p50={self.percentile(0.5):.3f} ms)")


class SpanRecorder:
    """Host spans of one recording, appended from any thread (a list
    append is atomic in CPython).

    ``spans`` holds ``(name, start_ns, duration_ns, ref)``: ``ref`` is the
    first request id for ``session.stage``, the batch's sequence number for
    every batch span. ``batches`` maps a batch's sequence number to its
    request ids, so the spans of one request can be joined."""

    def __init__(self):
        self.spans: list[tuple[str, int, int, int]] = []
        self.batches: dict[int, tuple[int, ...]] = {}

    def add(self, name: str, duration_ns: int, ref: int) -> None:
        """A span that ended now and lasted ``duration_ns``."""
        self.spans.append((name, time.time_ns() - duration_ns, duration_ns,
                           ref))
