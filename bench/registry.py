"""Finds what ``BENCHMARK.json`` names, by name, as files of their own:

- a configuration: ``configs/<name>.json`` (sizes, serving limits, the
  comparison's limits, ``source``, ``reduced``, ``assumed``);
- a traffic mix: ``traffic/<name>.json`` (loop, rate and arrival process
  or backlog, buckets, serving keywords, input pool, latency limit);
- a loop: ``loops/<name>.py``, whose ``run(...)`` drives one window of a
  mix that names it (``loadgen.py`` holds what the loops share);
- a plain model reference: ``models/<name>.py``;
- a per-layer metric: ``metrics/<name>.py``, whose ``read(ctx)`` returns the
  value or ``None`` where it finds nothing to read.

A later cell, mix, loop or metric is new files plus new entries in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def _path(base, kind: str, name: str, suffix: str) -> Path:
    """``<dir>/<kind>/<name><suffix>`` in the first of ``base`` (one
    directory or a list searched in order) that has it."""
    if not _NAME.fullmatch(name):
        raise ValueError(f"bad {kind} name {name!r}")
    dirs = [base] if isinstance(base, (str, Path)) else list(base)
    for d in dirs:
        path = Path(d) / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind[:-1]} {name!r} under "
                            f"{[str(d) for d in dirs]}")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer metrics
    (``trace`` true), as their ``BENCHMARK.json`` entries."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_config(name: str, base=BENCH_DIR) -> dict:
    cfg = json.loads(_path(base, "configs", name, ".json").read_text())
    cfg["name"] = name
    return cfg


def load_traffic(name: str, base=BENCH_DIR) -> dict:
    mix = json.loads(_path(base, "traffic", name, ".json").read_text())
    mix["name"] = name
    return mix


def _load_module(base, kind: str, name: str):
    path = _path(base, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_model(name: str, base=BENCH_DIR):
    return _load_module(base, "models", name)


def load_loop(name: str, base=BENCH_DIR):
    """The ``run(session, pool, seconds, mix, *, batch, seed, spans, rate)``
    of one loop, which returns the window's ``loadgen.WindowLog``."""
    return _load_module(base, "loops", name).run


def load_metric(name: str, base=BENCH_DIR):
    """The reader ``read(ctx) -> float | None`` of one per-layer metric."""
    return _load_module(base, "metrics", name).read
