#!/usr/bin/env python
"""Benchmark-artifact guard: schema-check ``BENCH_*.json`` and diff fresh
rows against the committed file — the nightly regression tripwire.

    PYTHONPATH=src python tools/bench_compare.py BENCH_table4_vgg16.json \
        --against git:HEAD --tol 0.5

Two passes:

1. **Schema check** — the file must be a JSON list of row dicts, every row
   carries string ``bench``/``name`` keys and JSON-scalar values, and rows
   with a known ``name`` carry that row's required metric keys (so a bench
   refactor cannot silently drop the metric CI archives). Always runs;
   failures exit non-zero.
2. **Regression diff** (with ``--against``) — rows are matched by ``name``
   against the baseline file (a path, or ``git:<ref>`` to read the version
   committed at ``<ref>``). Every shared numeric metric is reported. For
   the *ratio* metrics (speedups, rps ratios — machine-load-independent by
   construction), a drop of more than ``--tol`` fraction below the baseline
   fails the run (growth, for lower-is-better ratios); raw wall-clock/rps
   values are reported but never gated — CI runners are too noisy for
   absolute thresholds. Ratio gates that are only meaningful on specific
   hardware are skipped with a printed reason (the Pallas interpret-mode
   fallback ratio off-TPU; sharded-fleet ``rps_scaling`` on hosts with
   fewer cores than mesh devices). ``max_abs_diff`` (and the sharded
   ``pallas_sharded_max_abs_diff``) is gated absolutely: a row whose
   numerical-parity evidence worsens past ``--max-abs-diff`` (default
   1e-3) fails regardless of the baseline.

A baseline that does not exist (file missing at the ref — e.g. a brand-new
bench) skips the diff for that file with a note; the schema check still
applies. A fresh row the baseline file lacks (a newly added bench row, the
usual way a PR lands a new metric) is a WARN-and-record, never a failure:
its metrics are printed so the CI log archives the first measurement, and
it starts gating once the baseline catches up with it.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

# required metric keys per known row name — the contract between the bench
# writers and the CI artifact consumers
ROW_SCHEMAS: dict[str, set[str]] = {
    "runtime/jit_vs_interpreter": {"interp_ms", "jit_ms", "speedup",
                                   "max_abs_diff"},
    "runtime/single_vs_segmented": {"single_program_ms", "segmented_ms",
                                    "speedup", "max_abs_diff"},
    "runtime/fused_vs_blocked": {"fused_ms", "blocked_ms", "speedup",
                                 "fused_trace_compile_ms",
                                 "blocked_trace_compile_ms",
                                 "fused_jaxpr_ops", "blocked_jaxpr_ops",
                                 "jaxpr_op_reduction", "max_abs_diff"},
    "serving/batched_queue": {"session_rps", "direct_b1_rps",
                              "session_vs_direct_batched",
                              "session_vs_direct_single", "compile_ms",
                              "latency_p50_ms", "latency_p95_ms",
                              "max_abs_diff"},
    "serving/fleet_sharded": {"n_devices", "host_cores",
                              "session_rps_1dev", "session_rps_4dev",
                              "rps_scaling", "continuous_rps",
                              "bucketed_rps", "continuous_vs_bucketed",
                              "pallas_sharded_max_abs_diff",
                              "max_abs_diff"},
    "runtime/pallas_vs_xla": {"xla_ms", "pallas_ms", "pallas_over_xla",
                              "max_abs_diff"},
    "runtime/resnet18_single_program": {"n_instructions", "n_eltwise",
                                        "exec_ms", "gops", "strict_bitwise",
                                        "max_abs_diff_ref"},
    # parity key is dequant_max_abs_err, NOT max_abs_diff: int8 quantization
    # error is ~1e-1 in the dequantized logits by design, and the absolute
    # max_abs_diff gate (1e-3, fp32 bitwise-parity evidence) must not apply
    "runtime/int8_vs_fp32": {"fp32_ms", "int8_ms", "int8_speedup",
                             "top1_agreement_vgg16",
                             "top1_agreement_resnet18",
                             "executor_interp_bitwise",
                             "dequant_max_abs_err", "backend_mode"},
    # warm_over_cold_compile_ratio = warm-side warm_load_ms over cold-side
    # compile_ms: both are wall clocks for the SAME program in one process
    # from cleared caches, so the ratio is machine-load-independent and
    # gates as a lower-is-better key
    "serving/aot_cold_start": {"cold_compile_ms", "warm_load_ms",
                               "warm_over_cold_compile_ratio",
                               "max_abs_diff"},
    # survived/accounting_balanced/offenders_isolated are hard booleans
    # (liveness invariant), innocent_max_abs_diff must be exactly 0.0
    # (bisection re-runs the same executor at the same offsets), and
    # isolation_overhead_ratio gates as lower-is-better: both passes run
    # back-to-back in one process, so the ratio is load-independent
    "serving/fault_injection": {"fault_rate", "survived",
                                "accounting_balanced", "offenders_isolated",
                                "retries", "isolated",
                                "isolation_overhead_ratio",
                                "p95_clean_ms", "p95_faulty_ms",
                                "innocent_max_abs_diff"},
}

# higher-is-better ratio metrics: stable across machines, so they gate
RATIO_KEYS = ("speedup", "jaxpr_op_reduction", "session_vs_direct_batched",
              "session_vs_direct_single", "hybrid_speedup",
              "rps_scaling", "continuous_vs_bucketed", "int8_speedup",
              "top1_agreement_vgg16", "top1_agreement_resnet18")

# lower-is-better ratio metrics: gate on growth past tol instead of a drop
LOWER_RATIO_KEYS = ("pallas_over_xla", "warm_over_cold_compile_ratio",
                    "isolation_overhead_ratio")


def _ratio_gate_skipped(name, key, row) -> str | None:
    """Reason to skip ratio-gating this metric, or None to gate normally.

    * ``runtime/pallas_vs_xla`` in ``cpu_interpret`` mode measures the
      Pallas *interpreter* fallback, not kernel performance — its ratio is
      pure interpreter overhead and regresses with any added checking, so
      only the ``tpu`` mode gates.
    * ``rps_scaling`` (serving/fleet_sharded) needs one host core per mesh
      device to show real parallel speedup — on a smaller host the shards
      time-slice and the ratio measures scheduler overhead, so only hosts
      with enough cores gate it.
    """
    if (name == "runtime/pallas_vs_xla"
            and row.get("backend_mode") == "cpu_interpret"):
        return "cpu_interpret mode: ratio measures the interpreter fallback"
    if key == "rps_scaling":
        cores, ndev = row.get("host_cores", 0), row.get("n_devices", 0)
        if not (isinstance(cores, (int, float)) and isinstance(ndev, (int, float))) \
                or cores < ndev:
            return (f"host_cores={cores} < n_devices={ndev}: shards "
                    f"time-slice, scaling is not measurable")
    if (name == "runtime/int8_vs_fp32" and key == "int8_speedup"
            and str(row.get("backend_mode", "")).startswith("cpu")):
        return ("cpu host: XLA emulates int8 MACs in wider arithmetic, "
                "so the ratio measures emulation, not packed-MAC speedup")
    return None


def check_schema(path: Path) -> list[str]:
    errors = []
    try:
        rows = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable or not JSON: {e}"]
    if not isinstance(rows, list) or not rows:
        return [f"{path}: expected a non-empty JSON list of row dicts"]
    for i, row in enumerate(rows):
        where = f"{path}[{i}]"
        if not isinstance(row, dict):
            errors.append(f"{where}: not a dict")
            continue
        for key in ("bench", "name"):
            if not isinstance(row.get(key), str):
                errors.append(f"{where}: missing/non-string {key!r}")
        for k, v in row.items():
            if not isinstance(v, (str, int, float, bool)):
                errors.append(f"{where}: key {k!r} has non-scalar "
                              f"value {type(v).__name__}")
        name = row.get("name")
        if not isinstance(name, str):
            continue        # already reported; an unhashable name (e.g. a
                            # list) would crash the ROW_SCHEMAS lookup
        missing = ROW_SCHEMAS.get(name, set()) - set(row)
        if missing:
            errors.append(f"{where} ({row.get('name')}): missing required "
                          f"metric keys {sorted(missing)}")
    return errors


def _load_baseline(path: Path, against: str):
    if against.startswith("git:"):
        ref = against[4:] or "HEAD"
        proc = subprocess.run(
            ["git", "show", f"{ref}:{path.as_posix()}"],
            capture_output=True, text=True, cwd=path.parent or ".")
        if proc.returncode != 0:
            # only a genuinely-absent path is a benign skip (new bench);
            # any other git failure (not a repo, bad ref, absolute path,
            # shallow clone) means the tripwire is misconfigured and must
            # FAIL rather than silently gate nothing
            stderr = proc.stderr.strip()
            if ("does not exist" in stderr
                    or "exists on disk, but not in" in stderr):
                return None, None, f"{path} not present at {ref} (new bench?)"
            return None, f"git show {ref}:{path} failed: {stderr}", None
        try:
            return json.loads(proc.stdout), None, None
        except json.JSONDecodeError as e:
            return None, f"{path} at {ref} is not JSON: {e}", None
    base = Path(against)
    if not base.exists():
        return None, f"baseline {base} does not exist", None
    return json.loads(base.read_text()), None, None


def diff_rows(path: Path, against: str, tol: float,
              max_abs_diff: float) -> list[str]:
    baseline, error, skip_note = _load_baseline(path, against)
    if error is not None:
        return [error]
    if baseline is None:
        print(f"  diff skipped: {skip_note}")
        return []
    base_by_name = {r.get("name"): r for r in baseline}
    errors = []
    fresh_rows = json.loads(path.read_text())
    # a baseline row that disappears entirely is itself a regression — a
    # refactor must not silently drop a metric CI archives
    dropped = set(base_by_name) - {r.get("name") for r in fresh_rows}
    for name in sorted(dropped):
        errors.append(f"{path}: baseline row {name!r} is missing from the "
                      f"fresh artifact (bench dropped?)")
    new_rows = []
    for row in fresh_rows:
        name = row.get("name")
        base = base_by_name.get(name)
        if base is None:
            # warn-and-record, never fail: a new row is how a PR lands a
            # new metric — print its first measurements so the CI log
            # archives them; it gates once the committed baseline has it
            new_rows.append(name)
            metrics = ", ".join(
                f"{k}={v}" for k, v in sorted(row.items())
                if isinstance(v, (int, float)) and not isinstance(v, bool))
            print(f"  WARNING: {name}: new row, no baseline at {against} — "
                  f"recorded, not gated ({metrics})")
            continue
        for k, v in sorted(row.items()):
            bv = base.get(k)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not isinstance(bv, (int, float)):
                continue
            delta = v - bv
            print(f"  {name}.{k}: {bv} -> {v} ({delta:+.3g})")
            if k in RATIO_KEYS or k in LOWER_RATIO_KEYS:
                skip = _ratio_gate_skipped(name, k, row)
                if skip is not None:
                    print(f"  {name}.{k}: ratio gate skipped ({skip})")
                    continue
            if k in RATIO_KEYS and bv > 0 and v < bv * (1.0 - tol):
                errors.append(
                    f"{path}: {name}.{k} regressed {bv} -> {v} "
                    f"(> {tol:.0%} below baseline)")
            if k in LOWER_RATIO_KEYS and bv > 0 and v > bv * (1.0 + tol):
                errors.append(
                    f"{path}: {name}.{k} regressed {bv} -> {v} "
                    f"(> {tol:.0%} above baseline)")
            if k in ("max_abs_diff", "pallas_sharded_max_abs_diff") \
                    and v > max(bv, max_abs_diff):
                errors.append(
                    f"{path}: {name}.{k} worsened {bv} -> {v} "
                    f"(numerical-parity evidence)")
    if new_rows:
        print(f"  {len(new_rows)} new row(s) recorded without baseline "
              f"({', '.join(sorted(new_rows))}) — they gate once the "
              f"committed artifact includes them")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="*", default=None,
                    help="artifact files (default: BENCH_*.json in cwd)")
    ap.add_argument("--against", default=None,
                    help="baseline: a path, or git:<ref> for the committed "
                         "version (e.g. git:HEAD)")
    ap.add_argument("--tol", type=float, default=0.5,
                    help="allowed fractional drop in ratio metrics before "
                         "the diff fails (default 0.5 — CI runners are "
                         "noisy; ratios are load-independent but not "
                         "noise-free)")
    ap.add_argument("--max-abs-diff", type=float, default=1e-3,
                    help="absolute ceiling for max_abs_diff growth")
    args = ap.parse_args()
    files = [Path(f) for f in args.files] or sorted(
        Path(".").glob("BENCH_*.json"))
    if not files:
        print("ERROR: no BENCH_*.json artifacts found", file=sys.stderr)
        return 1
    errors = []
    for path in files:
        print(f"schema check: {path}")
        file_errors = check_schema(path)
        # gate the diff on THIS file's schema only — a malformed sibling
        # artifact must not suppress another file's regression check
        if args.against and not file_errors:
            print(f"diff vs {args.against}:")
            file_errors += diff_rows(path, args.against, args.tol,
                                     args.max_abs_diff)
        errors += file_errors
    for e in errors:
        print(f"ERROR: {e}", file=sys.stderr)
    print(f"bench check: {'FAIL' if errors else 'OK'} "
          f"({len(files)} artifact file(s))")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
