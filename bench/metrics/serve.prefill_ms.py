"""Device time of one run of the compiled ``admit`` program (prefill of one
request into its pages and its first token), averaged over the window's
admissions."""


def read(inp):
    runs = inp.trace.program_runs("admit")
    return 1e3 * sum(runs) / len(runs) if runs else None
