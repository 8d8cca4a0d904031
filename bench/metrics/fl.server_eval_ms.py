"""Device time per round of the operations under the name scope
``server_eval`` (the server evaluation that ``fedfits.run`` runs after
each round of its scan body).  Nothing is read where no operation carries
the scope."""


def read(inp):
    t = inp.trace.scope_time("server_eval")
    if t <= 0 or not inp.window["rounds"]:
        return None
    return 1e3 * t / inp.window["rounds"]
