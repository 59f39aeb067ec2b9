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
layer; an async copy (``copy-start``/``copy-done``) belongs to the layer
that reads what it copies.

- ``capture(names, session)``: ``trace.capture`` with the session's spans
  recorded too and every op's scope kept;
- ``reduce_scopes(events, device_ids)``: per scope, the union of its op
  intervals inside the window (so a ``while`` and the ops of its body count
  once), the share of op time that carries a scope, and ``device_ops``
  labelled with their scope (``while.1 [L2:conv.spat]``);
- ``layer_costs`` and ``layer_rows``: per ISA layer, FLOPs (direct
  convolution and the dense layers, 2 per MAC) and bytes (weights and
  biases, input and output maps at the configuration's dtype) from the
  reference's layer list, and per executor call the device ms and the
  roofline share: the larger of FLOPs over peak and bytes over the HBM
  rate, over the layer's time.

The scoped reduction reads ``None`` where less than ``MIN_SCOPED`` of the
op time in the window carries a scope: a program whose scopes went missing
then reads as unmeasured, not as fast.
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
_ASYNC_COPY = re.compile(r"\bcopy-(?:start|done)\(")
_CROSS_PROGRAM = "cross_program_prefetch_index"


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> layer scope, from a compiled module's text
    (``compiled.as_text()``). The copy XLA makes of the executor's input
    argument ``x`` (``op_name="x"``, its layout for the first layer) is the
    first layer's load, and takes that layer's scope. An async copy
    (``copy-start`` and its ``copy-done``, XLA's prefetch of a weight)
    takes the scope of the first scoped op that reads the copied value."""
    out, inputs, copies = {}, [], []
    users: dict[str, list[str]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        for operand in _OPERAND.findall(rest.split(", metadata=")[0]):
            users.setdefault(operand, []).append(name)
        op = _OP_NAME.search(rest)
        s = SCOPE.search(op.group(1)) if op else None
        if s:
            out[name] = s.group(1)
        elif op and op.group(1) == "x":
            inputs.append(name)
        elif _ASYNC_COPY.search(rest):
            copies.append(name)
    if out:
        first = min(out.values(), key=_layer_id)
        out.update(dict.fromkeys(inputs, first))

    def reader_scope(name):
        """The scope of the nearest scoped op that reads ``name``."""
        todo, seen = [name], {name}
        while todo:
            for u in users.get(todo.pop(0), ()):
                if u in out:
                    return out[u]
                if u not in seen:
                    seen.add(u)
                    todo.append(u)
        return None
    for name in copies:
        if (s := reader_scope(name)) is not None:
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
    """Per-layer device numbers over the ``bench.window`` span, averaged
    over ``device_ids``, or ``None`` where the trace holds no window, no
    device events or too little scoped op time:

    - ``scope_s``: per scope, the union of its op intervals;
    - ``scoped_share``: the union of the scoped op intervals over the union
      of all op intervals (at least ``MIN_SCOPED``);
    - ``module_s``: as ``trace.reduce``;
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
    if share < MIN_SCOPED:
        return None
    n = len(device_ids)

    def label(name):
        by = name_scope.get(name)
        return f"{name} [{max(by, key=by.get)}]" if by else name
    return {
        "scope_s": {s: t / n / 1e9 for s, t in sorted(
            scope_t.items(), key=lambda kv: _layer_id(kv[0]))},
        "scoped_share": share,
        "module_s": base["module_s"],
        "device_ops": [[label(name), t] for name, t in base["device_ops"]],
    }


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


def layer_costs(model, sizes: dict, rows: int, dtype_bytes: int,
                program_layers) -> list:
    """Per ISA layer of the reference (``model.layers(sizes)``), ``(kind,
    flops, bytes)`` of one executor call on ``rows`` images: FLOPs of
    direct convolution and the dense layers (2 per MAC; pooling counts
    none), bytes of weights and biases once plus each image's input and
    output maps, all at ``dtype_bytes``.

    It describes VGG's layers alone: 3x3 stride-1 "same" convolutions,
    2x2/2 max pools and dense layers. It raises on any other layer tuple,
    kernel or shape, and where the program's layers (``program_layers``,
    ``(layer_id, kind)`` of each ISA layer) are not the reference's, one
    for one and numbered from 0 in order."""
    import jax

    layers = model.layers(sizes)
    shapes = jax.eval_shape(lambda k: model.init_params(k, sizes),
                            jax.random.key(0))
    if [tuple(p) for p in program_layers] != [
            (i, layer[0]) for i, layer in enumerate(layers)]:
        raise ValueError(f"the program's layers {list(program_layers)} are "
                         f"not the reference's {[l[0] for l in layers]}")
    out, pi, prev = [], 0, None     # prev: the map before, (h, c) or (d,)
    for layer in layers:
        kind = layer[0]
        if kind == "conv" and len(layer) == 4:
            _, h, c, k = layer
            w = tuple(shapes[pi][0].shape)
            pi += 1
            if w != (3, 3, c, k):
                raise ValueError(f"conv weight {w} is not 3x3 {c}->{k}")
            flops = 2 * h * h * 9 * c * k
            weights, maps = 9 * c * k + k, h * h * (c + k)
            inp, nxt = (h, c), (h, k)
        elif kind == "pool" and len(layer) == 3 and layer[1] % 2 == 0:
            _, h, c = layer
            flops, weights = 0, 0
            maps = h * h * c + (h // 2) ** 2 * c
            inp, nxt = (h, c), (h // 2, c)
        elif kind == "fc" and len(layer) == 4:
            _, d_in, d_out, _ = layer
            w = tuple(shapes[pi][0].shape)
            pi += 1
            if w != (d_in, d_out):
                raise ValueError(f"dense weight {w} is not {d_in}x{d_out}")
            flops = 2 * d_in * d_out
            weights, maps = d_in * d_out + d_out, d_in + d_out
            inp, nxt = (d_in,), (d_out,)
            if prev is not None and len(prev) == 2:
                prev = (prev[0] * prev[0] * prev[1],)
        else:
            raise ValueError(f"no cost is described for layer {layer}")
        if prev is not None and inp != prev:
            raise ValueError(f"layer {layer} does not read the map {prev} "
                             f"the layer before it writes")
        prev = nxt
        out.append((kind, rows * flops, dtype_bytes * (weights + rows * maps)))
    if pi != len(shapes):
        raise ValueError(f"{len(shapes)} weights for {pi} weighted layers")
    return out


def layer_rows(scope_s: dict, calls: int, costs: list, peak_ops: float,
               hbm_bytes_per_s: float, prefetched=()) -> list[dict]:
    """Per scope: device ms per executor call, and the roofline share with
    the bound that sets it (``compute`` or ``memory``). The share reads
    ``None``, with the reason in ``unmeasured``, for a layer in
    ``prefetched`` (``prefetched_scopes``: its weights move outside its
    time) and for a share over 100% (its bytes or FLOPs are worked in
    another scope's ops, as a pool fused into the convolution before it)."""
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
            "roofline": None if unmeasured else share,
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "unmeasured": unmeasured})
    return rows
