"""Device-idle time per decode step inside the serving loop's
``serve.decode`` and ``serve.bookkeep`` spans (the decode program and its
sync; the slot walk, eviction and telemetry row), over the window's decode
steps.  Nothing is read where the window holds no such span."""

from bench import overlap


def read(inp):
    iv = overlap.covered(inp.trace, {"serve.decode", "serve.bookkeep"})
    if iv is None or not inp.window["decode_steps"]:
        return None
    return 1e3 * overlap.idle_inside_s(inp.trace, iv) \
        / inp.window["decode_steps"]
