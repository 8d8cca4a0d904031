"""Device time under the scope ``paged_decode_window`` (the windowed paged
flash-decode kernel of every window layer) per decode step of the window.
Nothing is read where the window holds no such scope or no decode step."""


def read(inp):
    t = inp.trace.scope_time("paged_decode_window")
    if t <= 0 or not inp.window["decode_steps"]:
        return None
    return 1e3 * t / inp.window["decode_steps"]
