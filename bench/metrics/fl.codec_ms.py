"""Device time per round of the operations under the name scope ``codec``
(the uplink codec with error feedback, ``error_feedback.compress``, and the
wire-byte accounting).  Nothing is read where no operation carries the
scope."""


def read(inp):
    t = inp.trace.scope_time("codec")
    if t <= 0 or not inp.window["rounds"]:
        return None
    return 1e3 * t / inp.window["rounds"]
