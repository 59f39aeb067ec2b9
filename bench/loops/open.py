"""Open loop (MLPerf Inference's Server scenario): single-image requests sent
on the schedule of the mix's arrival process (``loadgen.arrival_times``) at
``rate_per_s``, whatever the server does."""
import time

from bench.loadgen import WindowLog, arrival_times, span


def run(session, pool, seconds: float, mix: dict, *, batch: int, seed: int,
        spans: list | None = None, rate: float | None = None) -> WindowLog:
    rate = mix["rate_per_s"] if rate is None else rate
    n_pool = len(pool)
    offsets = arrival_times(mix, rate, seconds, seed)
    log = WindowLog(time.monotonic(), seconds, mix.get("expected_errors", ()))
    for i, due in enumerate(log.t_start + offsets):
        if due >= log.t_end:
            break
        wait = due - time.monotonic()
        if wait > 0:
            with span(spans, "bench.sleep"):
                time.sleep(wait)
        with span(spans, "bench.submit"):
            sent = time.monotonic()
            fut = session.submit(pool[i % n_pool])
        log.track(fut, float(due), sent, i % n_pool)
    wait = log.t_end - time.monotonic()
    if wait > 0:
        with span(spans, "bench.sleep"):
            time.sleep(wait)
    return log
