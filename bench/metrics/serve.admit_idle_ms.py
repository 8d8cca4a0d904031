"""Device-idle time per admission inside the serving loop's
``serve.admit`` spans (prompt padding, the admit program, its sync and the
ledger update), over the window's admissions.  Nothing is read where the
window holds no such span or no admission."""

from bench import overlap


def read(inp):
    iv = overlap.covered(inp.trace, {"serve.admit"})
    if iv is None or not inp.window["admits"]:
        return None
    return 1e3 * overlap.idle_inside_s(inp.trace, iv) / inp.window["admits"]
