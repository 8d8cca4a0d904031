"""The windowed paged flash-decode kernel's share of its memory roofline:
the KV bytes the window layers attend in the window's decode steps at
bfloat16 (``decode_rows_window``, the program's per-layer count of rows
inside the window, x K and V x heads x head_dim x window layers;
``work.kv_bytes_per_row``) over the device time under the scope
``paged_decode_window``, at the chip's HBM bandwidth.  Nothing is read
where the window holds no such scope or no row count."""


def read(inp):
    t = inp.trace.scope_time("paged_decode_window")
    rows = inp.window.get("decode_rows_window")
    if t <= 0 or not rows:
        return None
    least = inp.work.kv_bytes_per_row(inp.config["config"],
                                      "sliding_attention") * rows
    return 100.0 * least / inp.peaks["hbm_bw"] / t
