"""Closed loop (MLPerf Inference's Offline and SingleStream scenarios):
``backlog`` requests stay outstanding, and each time ``refill`` of them have
completed as many new ones go in. Both count in the mix's ``unit``:
``"batch"`` (the session's largest bucket) or ``"request"``."""
import time

from bench.loadgen import WindowLog, span


def run(session, pool, seconds: float, mix: dict, *, batch: int, seed: int,
        spans: list | None = None, rate: float | None = None) -> WindowLog:
    unit = batch if mix["unit"] == "batch" else 1
    backlog, refill = mix["backlog"] * unit, mix["refill"] * unit
    n_pool = len(pool)
    log = WindowLog(time.monotonic(), seconds, mix.get("expected_errors", ()))
    nxt = 0

    def send(k):
        nonlocal nxt
        idx = [(nxt + j) % n_pool for j in range(k)]
        nxt += k
        with span(spans, "bench.submit"):
            now = time.monotonic()
            futs = session.submit_many([pool[i] for i in idx])
        for f, i in zip(futs, idx):
            log.track(f, now, now, i)

    send(backlog)
    while time.monotonic() < log.t_end:
        with span(spans, "bench.wait"):
            if not log.wait_resolved(len(log.due) - backlog + refill, 60.0):
                break
        if time.monotonic() < log.t_end:
            send(refill)
    return log
