"""The int8 aggregation's share of its memory roofline: the least bytes it
needs (codes and scales read once, the f32 aggregate written once;
``work.agg_least_bytes``) over all device time under the name scope
``aggregate``, at the chip's HBM bandwidth."""


def read(inp):
    if not inp.trace.has_scopes():
        return None
    t = inp.trace.scope_time("aggregate")
    if t <= 0 or not inp.window["rounds"]:
        return None
    least = inp.work.agg_least_bytes(inp.config["config"], inp.traffic) \
        * inp.window["rounds"]
    return 100.0 * least / inp.peaks["hbm_bw"] / t
