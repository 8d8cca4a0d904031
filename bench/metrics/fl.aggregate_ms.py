"""Device time per round of the operations under the name scope
``aggregate`` (the robust pipeline, the fused int8 dequant, or the
size-weighted mean).  Nothing is read where the trace carries no name
scopes."""


def read(inp):
    if not inp.trace.has_scopes() or not inp.window["rounds"]:
        return None
    return 1e3 * inp.trace.scope_time("aggregate") / inp.window["rounds"]
