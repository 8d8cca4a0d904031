"""The serving steps' share of the chip's bf16 peak: the operations the
window's tokens require (``work.serve_flops``: real prompt tokens and
decoded tokens, no padding) over the traced window."""


def read(inp):
    flops = inp.work.serve_flops(inp.config["config"], inp.window)
    return 100.0 * flops / inp.trace.window_s / inp.peaks["flops_bf16"]
