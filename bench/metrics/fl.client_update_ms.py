"""Device time per round of the operations under the name scope
``client_update`` (the vmapped local update and the fitness evaluations).
Nothing is read where the trace carries no name scopes."""


def read(inp):
    if not inp.trace.has_scopes() or not inp.window["rounds"]:
        return None
    return 1e3 * inp.trace.scope_time("client_update") / inp.window["rounds"]
