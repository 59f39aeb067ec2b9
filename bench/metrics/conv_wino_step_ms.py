"""PEs: device time of the Winograd convolution layers (scopes
``L*:conv.wino``) inside the traced window, averaged over the chips, over
the executor calls the session counted there. Reads nothing where too little
of the window's op time carries a layer scope, or no layer is Winograd."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.scope_s or not t.counters["batches"]:
        return None
    s = [v for k, v in t.scope_s.items() if k.endswith(":conv.wino")]
    return sum(s) * 1e3 / t.counters["batches"] if s else None
