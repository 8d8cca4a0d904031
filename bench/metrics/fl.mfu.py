"""The whole round's share of the chip's bf16 peak: the operations the
window's rounds require (``work.round_flops``) over the traced window."""


def read(inp):
    flops = inp.work.round_flops(inp.config["config"], inp.traffic) \
        * inp.window["rounds"]
    return 100.0 * flops / inp.trace.window_s / inp.peaks["flops_bf16"]
