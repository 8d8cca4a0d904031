"""The paged flash-decode kernel's share of its memory roofline: the live
KV bytes of the window's decode steps at bfloat16 (rows attended x K and V
x heads x head_dim x layers; ``work.kv_bytes_per_row``) over the kernel's
device time, at the chip's HBM bandwidth."""

from bench.kernels import is_paged_decode


def read(inp):
    t = inp.trace.device_time(is_paged_decode)
    if t <= 0:
        return None
    least = inp.work.kv_bytes_per_row(inp.config["config"]) \
        * inp.window["decode_rows"]
    return 100.0 * least / inp.peaks["hbm_bw"] / t
