"""Device time of one run of the compiled ``decode`` program, averaged over
the window's runs."""


def read(inp):
    runs = inp.trace.program_runs("decode")
    return 1e3 * sum(runs) / len(runs) if runs else None
