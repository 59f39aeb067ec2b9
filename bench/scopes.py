"""Device time per ISA layer, from the layer scopes the program puts in each
op's metadata, and the program's own host spans on the trace's clock.

The executor wraps each layer's ops in ``jax.named_scope("L{id}:{kind}")``
(``kind``: ``conv.spat``, ``conv.wino``, ``pool``, ``fc``, ``eltwise``,
``dw``), and ``ServingSession.record_spans()`` records the session's host
spans (``session.*``) on the ``time.time_ns`` clock. This module reads both
beside what ``trace.py`` reads, in the same plain form plus, per device, an
``op_scopes`` list aligned with its ``ops``.

A TPU v5e trace's op events carry no ``op_name``: their stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale Multiplier``.
So the scopes come from the compiled executor's HLO text, by instruction
name (``executor_hlo``, ``hlo_scopes``). XLA's own instructions carry no
metadata: a ``while`` that XLA made of a convolution has its layer's scope,
its body's ops have none and take the scope of the op that encloses them in
time; the layout copy of the input, named ``x``, belongs to the first
layer. Any other instruction of the entry computation that XLA made with
no ``op_name`` (as the in-place update chain of a stacked tensor, or an
async copy), which no op encloses in time, takes the scope of its nearest
scoped operand, or failing that of its nearest scoped reader.

- ``capture(names, session)``: ``trace.capture`` with the session's spans
  recorded too and every op's scope kept;
- ``reduce_scopes(events, device_ids)``: ``trace.reduce``'s numbers, and per
  scope the union of its op intervals inside the window (so a ``while`` and
  the ops of its body count once), the share of op time that carries a
  scope, and ``device_ops`` labelled with their scope (``while.1
  [L2:conv.spat]``);
- ``check_costs`` and ``layer_rows``: the model reference's ``layer_costs``
  (FLOPs and bytes per ISA layer, ``bench/registry.py``) held to the
  program's layers one for one, and per executor call the device ms and the
  roofline share of each layer: the larger of FLOPs over peak and bytes over
  the HBM rate, over the layer's time.

The scoped reduction's ``scope_s`` reads ``None`` where less than
``MIN_SCOPED`` of the op time in the window carries a scope: a program whose
scopes went missing then reads as unmeasured, not as fast.
"""
from __future__ import annotations

import contextlib
import re

from bench import trace as tracing

SCOPE = re.compile(r"(?:^|/)(L\d+:[a-z.]+)(?:/|$)")
MIN_SCOPED = 0.95
_HLO_LINE = re.compile(r"\s*(?:ROOT )?%?([\w.-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.-]+)")
_CROSS_PROGRAM = "cross_program_prefetch_index"


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> layer scope, from a compiled module's text
    (``compiled.as_text()``). The copy XLA makes of the executor's input
    argument ``x`` (``op_name="x"``, its layout for the first layer) is the
    first layer's load, and takes that layer's scope. Any other instruction
    with no ``op_name`` in the entry computation (in a text with no
    computation headers, anywhere) takes the scope of its nearest scoped
    operand, or failing that of its nearest scoped reader; one in a
    ``while`` body is left to the op that encloses it in time. An async
    copy of a weight (``copy-start`` and its ``copy-done``, XLA's prefetch)
    reads only a parameter, which carries no scope, so it takes the scope
    of the first scoped op that reads the copied value."""
    out, inputs, bare = {}, [], []
    operands: dict[str, list[str]] = {}
    users: dict[str, list[str]] = {}
    entry = True
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if not m:
            if line.rstrip().endswith("{"):     # a computation's header
                entry = line.lstrip().startswith("ENTRY")
            continue
        name, rest = m.groups()
        operands[name] = _OPERAND.findall(rest.split(", metadata=")[0])
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)
        op = _OP_NAME.search(rest)
        s = SCOPE.search(op.group(1)) if op else None
        if s:
            out[name] = s.group(1)
        elif op and op.group(1) == "x":
            inputs.append(name)
        elif op is None and entry:
            bare.append(name)
    if out:
        first = min(out.values(), key=_layer_id)
        out.update(dict.fromkeys(inputs, first))

    def nearest(name, edges, scoped):
        """The scope of the nearest instruction in ``scoped`` reached from
        ``name`` along ``edges`` (operands or readers)."""
        todo, seen = [name], {name}
        while todo:
            for u in edges.get(todo.pop(0), ()):
                if u in scoped:
                    return scoped[u]
                if u not in seen:
                    seen.add(u)
                    todo.append(u)
        return None
    for edges in (operands, users):
        known = dict(out)
        for name in bare:
            if name not in out and (s := nearest(name, edges, known)):
                out[name] = s
    return out


def prefetched_scopes(hlo_text: str, names: dict[str, str]) -> set[str]:
    """The scopes that read a weight XLA prefetches across programs
    (``copy-start ... cross_program_prefetch_index``): that weight's bytes
    move while other layers run, or not at all where the copy from the last
    call is still there, so the layer's own time does not hold them."""
    out = set()
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m and _CROSS_PROGRAM in line and m.group(1) in names:
            out.add(names[m.group(1)])
    return out


def executor_hlo(jax, session) -> str:
    """The compiled text of the session's largest bucket's executor.

    Its instruction names are those of the executable the session runs
    only where this compile is a persistent-cache hit of it, as it is with
    the benchmark's cache on: a second, fresh compile on a TPU v5e numbered
    its instructions otherwise, and matched no op of the trace. A trace
    that holds several buckets' modules maps their ops by these names too,
    so only a cell that runs one bucket is read exactly."""
    acc, b = session.acc, session.buckets[-1]
    entry, params = acc.runtime.executor_entry(b, acc.input_dtype,
                                               donate_input=True)
    x = jax.ShapeDtypeStruct((b, *acc.input_shape), acc.input_dtype)
    return entry.fn.lower(params, x).compile().as_text()


def attach_scopes(events: dict, names: dict[str, str]) -> None:
    """Give each device of ``events`` (``trace.load_events``' plain form)
    ``op_scopes``: the layer scope of each op event by its HLO name in
    ``names`` (``None`` where it has none)."""
    for dev in events["devices"].values():
        dev["op_scopes"] = [names.get(name) for name, _, _ in dev["ops"]]


@contextlib.contextmanager
def capture(names: dict[str, str], session=None):
    """``trace.capture``, keeping each op's layer scope (``names``, from
    ``hlo_scopes``), and with ``session`` given its ``record_spans()`` on
    for the block: its spans join the benchmark's in ``host`` as ``[name,
    start_ns, duration_ns]`` and its batches' request ids go to
    ``batches``."""
    with tracing.capture() as rec:
        with (session.record_spans() if session is not None
              else contextlib.nullcontext()) as spans:
            yield rec
        if spans is not None:
            rec["host"] += [[n, a, d] for n, a, d, _ in spans.spans]
            rec["batches"] = {str(k): list(v)
                              for k, v in spans.batches.items()}
    attach_scopes(rec, names)


def reduce_scopes(events: dict, device_ids) -> dict | None:
    """``trace.reduce``'s numbers over the ``bench.window`` span, with the
    per-layer ones beside them, averaged over ``device_ids``; ``None`` where
    ``trace.reduce`` reads ``None`` (no window or no device events):

    - ``scope_s``: per scope, the union of its op intervals; ``None`` where
      ``scoped_share`` is under ``MIN_SCOPED``;
    - ``scoped_share``: the union of the scoped op intervals over the union
      of all op intervals;
    - ``device_ops``: ``trace.reduce``'s, each name followed by the scope
      that holds most of its time.
    """
    base = tracing.reduce(events, device_ids)
    if base is None:
        return None
    w0, wdur = next((a, d) for n, a, d in events["host"]
                    if n == tracing.WINDOW_SPAN)
    w1 = w0 + wdur
    scope_t: dict[str, float] = {}
    name_scope: dict[str, dict[str, float]] = {}
    scoped = total = 0.0
    for d in device_ids:
        dev = events["devices"][str(d)]
        per_scope: dict[str, list] = {}
        every, covered = [], []
        op_scopes = dev.get("op_scopes") or [None] * len(dev["ops"])
        for (name, start, dur), scope in zip(
                dev["ops"], _enclosed(dev["ops"], op_scopes)):
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            every.append((a, b))
            if scope is None:
                continue
            covered.append((a, b))
            per_scope.setdefault(scope, []).append((a, b))
            by = name_scope.setdefault(name, {})
            by[scope] = by.get(scope, 0.0) + (b - a)
        for scope, iv in per_scope.items():
            scope_t[scope] = scope_t.get(scope, 0.0) + _length(iv)
        scoped += _length(covered)
        total += _length(every)
    share = scoped / total if total else 0.0
    n = len(device_ids)

    def label(name):
        by = name_scope.get(name)
        return f"{name} [{max(by, key=by.get)}]" if by else name
    return dict(
        base,
        scope_s=None if share < MIN_SCOPED else {
            s: t / n / 1e9 for s, t in sorted(
                scope_t.items(), key=lambda kv: _layer_id(kv[0]))},
        scoped_share=share,
        device_ops=[[label(name), t] for name, t in base["device_ops"]])


def _enclosed(ops, op_scopes) -> list:
    """``op_scopes`` with each unscoped op given the scope of the innermost
    scoped op still open when it starts and ending after it (the body ops
    of a ``while`` take the ``while``'s scope)."""
    out = list(op_scopes)
    open_: list = []    # (end, scope) of the scoped ops open, innermost last
    for i in sorted(range(len(ops)), key=lambda k: (ops[k][1], -ops[k][2])):
        _, start, dur = ops[i]
        while open_ and open_[-1][0] <= start:
            open_.pop()
        if out[i] is not None:
            open_.append((start + dur, out[i]))
        elif open_ and open_[-1][0] >= start + dur:
            out[i] = open_[-1][1]
    return out


def _length(intervals) -> float:
    return sum(b - a for a, b in tracing._union(intervals))


def _layer_id(scope: str) -> int:
    return int(scope[1:scope.index(":")])


def check_costs(costs: list, program_layers) -> list:
    """``costs`` (a model reference's ``layer_costs``: ``(kind, flops,
    bytes)`` per ISA layer) where they are the program's layers
    (``program_layers``, ``(layer_id, kind)`` of each) one for one, numbered
    from 0 in order; raises ``ValueError`` otherwise."""
    if [tuple(p) for p in program_layers] != [
            (i, c[0]) for i, c in enumerate(costs)]:
        raise ValueError(f"the program's layers {list(program_layers)} are "
                         f"not the reference's {[c[0] for c in costs]}")
    return costs


def layer_rows(scope_s: dict, calls: int, costs: list, peak_ops: float,
               hbm_bytes_per_s: float, prefetched=()) -> list[dict]:
    """Per scope: device ms per executor call, the least ms the chip could
    take for the layer's FLOPs and bytes (``least_ms``), and the roofline
    share, ``least_ms`` over the layer's ms, with the bound that sets it
    (``compute`` or ``memory``), and whether the layer is in ``prefetched``
    (``prefetched_scopes``: its weights move outside its time). The share
    reads ``None`` in the table, with the reason in ``unmeasured``, for a
    prefetched layer and for a share over 100% (its bytes or FLOPs are
    worked in another scope's ops, as a pool fused into the convolution
    before it); ``least_ms`` and ``ms_per_call`` stay, so a metric that
    sums them sees such a share."""
    rows = []
    for scope, seconds in scope_s.items():
        kind, flops, nbytes = costs[_layer_id(scope)]
        t = seconds / calls
        t_flops, t_bytes = flops / peak_ops, nbytes / hbm_bytes_per_s
        share = 100.0 * max(t_flops, t_bytes) / t if t > 0 else None
        unmeasured = None
        if scope in prefetched:
            unmeasured = "weights prefetched across programs"
        elif share is None or share > 100.0:
            unmeasured = "work done in another scope's ops"
        rows.append({
            "scope": scope, "ms_per_call": t * 1e3,
            "least_ms": max(t_flops, t_bytes) * 1e3,
            "roofline": None if unmeasured else share,
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "prefetched": scope in prefetched, "unmeasured": unmeasured})
    return rows
