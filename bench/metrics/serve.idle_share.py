"""Share of the traced window in which no operation ran on the device,
under the serving host loop."""


def read(inp):
    return 100.0 * (1.0 - inp.trace.busy_s / inp.trace.window_s)
