"""Share of the window in which the device is idle while the round driver
stages the next chunk (idle gaps inside ``driver.stage`` spans).  Nothing
is read where the window holds no such span."""

from bench import overlap


def read(inp):
    iv = overlap.covered(inp.trace, {"driver.stage"})
    if iv is None:
        return None
    return 100.0 * overlap.idle_inside_s(inp.trace, iv) / inp.trace.window_s
