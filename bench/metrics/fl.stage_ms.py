"""Host time per round inside the round driver's ``driver.stage`` spans
(the next chunk's batches: ``batch_fn`` calls, stacking, ``device_put``),
clipped to the window.  Nothing is read where the window holds no such
span."""

from bench import overlap


def read(inp):
    iv = overlap.covered(inp.trace, {"driver.stage"})
    if iv is None or not inp.window["rounds"]:
        return None
    return 1e3 * overlap.length_s(iv) / inp.window["rounds"]
