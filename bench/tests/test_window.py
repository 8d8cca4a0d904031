"""The window-layer serving cell on the CPU at a size a test run holds:
``correct`` through ``engines/serve_window.py`` with Mellum2's layer pattern
(three window layers, one full layer with YaRN) at small widths, the
adapter's windowed counts, and the two readers of the windowed kernel on a
synthetic trace."""
import copy
import json

import pytest

from bench import common, trace

BENCH = json.loads((common.ROOT / "BENCHMARK.json").read_text())
CELL = "serve_mellum_code_long"


def _files():
    wl = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    config = json.loads((common.ROOT / entry["file"]).read_text())
    traffic = json.loads((common.ROOT / "bench" / "traffic"
                          / f"{wl['traffic']}.json").read_text())
    return wl, config, traffic


def _engine():
    return common.load_module(common.ROOT / "bench" / "engines"
                              / "serve_window.py")


def _metric(name):
    return common.load_module(common.ROOT / "bench" / "metrics"
                              / f"{name}.py")


def _small():
    """Mellum2's layer pattern, YaRN and routing at small widths: d 256,
    8 q and 2 kv heads of 32, 8 experts (top 2), window 16 over pages of 4
    (rings of 5 pages), YaRN from an original length of 1024 so that its
    ramp spans frequency indices 1 to 7 of 16; contexts up to 88 rows
    wrap each ring four times."""
    wl, config, traffic = _files()
    config = copy.deepcopy(config)
    small = {"d_model": 256, "n_heads": 8, "n_kv_heads": 2, "head_dim": 32,
             "d_ff": 64, "n_experts": 8, "top_k": 2, "vocab_size": 512,
             "capacity_factor": 4.0, "sliding_window": 16,
             "yarn_original_max_pos": 1024, "dtype": "float32",
             "param_dtype": "float32"}
    config["config"].update(small)
    config["program"]["overrides"].update(small)
    traffic = copy.deepcopy(traffic)
    traffic.update(slots=4, page_size=4, prompt_pad=64, max_len=96,
                   num_pages=96, warmup_steps=2, check_requests=4)
    traffic["mix"] = {"prompt": [24, 64], "output": [8, 24], "block": 4,
                      "n_blocks": 60, "warm": 4}
    return wl, config, traffic


def _run(seed=2 ** 33 + 5):
    wl, config, traffic = _small()
    ctx = common.Context(workload=wl, config=config, traffic=traffic,
                         seed=seed, seconds=1.0, window=common.Window(None))
    return _engine().run(ctx)


def test_sound_run_is_correct_and_counts_window_rows():
    out = _run()
    assert out["e2e"]["tokens_per_s"] > 0
    assert all(value <= limit for _, value, limit in out["checks"])
    w = out["window"]
    # the program's full-layer count is the recorder's own (the run
    # raises where they differ); window rows are clipped to 16 a slot
    assert w["decode_rows_full"] == w["decode_rows"] > 0
    assert w["decode_rows_window"] <= 16 * w["decode_tokens"]
    assert w["decode_rows_window"] < w["decode_rows_full"]


@pytest.mark.parametrize("plen,window,pairs", [
    (5, 16, 15),                    # the window does not bind
    (16, 16, 136),
    (20, 16, 136 + 4 * 16),         # four queries see exactly 16 keys
    (3072, 1024, 1024 * 1025 // 2 + 2048 * 1024)])
def test_prompt_pairs_window_hand_count(plen, window, pairs):
    assert _engine().prompt_pairs_window(plen, window) == pairs


class _Rec:
    """A recorder's lists, as ``engines/serve.py`` fills them."""

    def __init__(self, full=None):
        self.tokens = {0: [(1, 0.5), (2, 1.5), (3, 2.5)]}
        self.admits = [(0.5, 20)]
        self.steps = [(1.5, 1, 21, 1.0), (2.5, 1, 22, 1.0)]
        self.rows = [(1.5, 21.0 if full is None else full, 16.0),
                     (2.5, 22.0, 16.0)]


def test_window_readings_fields():
    w = _engine().window_readings(_Rec(), 0.0, 3.0, 16)
    assert w["decode_rows"] == w["decode_rows_full"] == 43
    assert w["decode_rows_window"] == 32
    assert w["prompt_pairs"] == 210
    assert w["prompt_pairs_window"] == 136 + 4 * 16


def test_window_readings_refuse_a_miscount():
    with pytest.raises(RuntimeError):
        _engine().window_readings(_Rec(full=20.0), 0.0, 3.0, 16)


def _trace():
    """Two decode runs' ops: the windowed kernel under its scope, the full
    layer's kernel and a fusion beside it."""
    scope = "jit(decode)/while/body/closed_call/checkpoint"
    ops = [trace.Op("paged_decode_window.3", 0, 3_000_000,
                    f"{scope}/paged_decode_window/paged_decode_window",
                    "custom-call", "decode", 0, "tpu_custom_call"),
           trace.Op("paged_decode.7", 3_000_000, 2_000_000,
                    f"{scope}/paged_decode/paged_decode", "custom-call",
                    "decode", 0, "tpu_custom_call"),
           trace.Op("paged_decode_window.3", 5_000_000, 3_000_000,
                    f"{scope}/paged_decode_window/paged_decode_window",
                    "custom-call", "decode", 0, "tpu_custom_call"),
           trace.Op("fusion.1", 8_000_000, 1_000_000, scope, "fusion",
                    "decode", 0)]
    return trace.Trace(ops, (0, 10_000_000), [], 1, [])


def _inp(window, tr=None):
    _, config, _ = _files()
    work = common.load_module(common.ROOT / "bench" / "work"
                              / f"{config['name']}.py")
    return trace.LayerInput(trace=tr or _trace(), window=window, work=work,
                            peaks={"hbm_bw": 819e9}, config=config,
                            traffic={})


def test_window_attn_ms_reads_the_scope_per_step():
    read = _metric("serve.window_attn_ms").read
    assert read(_inp({"decode_steps": 2})) == pytest.approx(3.0)
    empty = trace.Trace([], (0, 10_000_000), [], 1, [])
    assert read(_inp({"decode_steps": 2}, empty)) is None


def test_paged_window_decode_roofline_hand_count():
    read = _metric("paged_window_decode_roofline").read
    rows = 64 * 1024
    # K and V x 4 heads x 128 x 3 window layers x 2 bytes per row
    least = rows * 2 * 4 * 128 * 3 * 2
    assert read(_inp({"decode_rows_window": rows})) == pytest.approx(
        100.0 * least / 819e9 / 6e-3)
    # a program without the counters reads nothing
    assert read(_inp({})) is None
