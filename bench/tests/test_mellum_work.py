"""Work functions of mellum2-12b-a2.5b against hand counts at the published
widths and the program's own parameter tree (``jax.eval_shape``, nothing
allocated)."""
import json
import math

import jax

from bench import common


def _work(name):
    return common.load_module(common.ROOT / "bench" / "work" / f"{name}.py")


def _program_params(config):
    from repro.models.model import build

    cfg = common.program_config(config)
    tree = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    return sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(tree))


MELLUM = json.loads(
    (common.ROOT / "bench/configs/mellum2-12b-a2.5b.json").read_text())


def test_mellum_params_match_program():
    """One period of 4 layers at published widths, untied head: 2.124e9
    parameters held on the chip."""
    w, c = _work("mellum2-12b-a2.5b"), MELLUM["config"]
    attn = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    layer = attn + 2304 * 64 + 64 * 3 * 2304 * 896 + 2 * 2304
    assert w.total_params(c) == 4 * layer + 2 * 98_304 * 2304 + 2304 \
        == 2_123_976_960 == _program_params(MELLUM)


def test_mellum_active_params_and_kv_hand_count():
    w, c = _work("mellum2-12b-a2.5b"), MELLUM["config"]
    attn = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    per_layer = attn + 2304 * 64 + 8 * 3 * 2304 * 896
    assert w.active_params(c) == 4 * per_layer + 2304 * 98_304
    assert w.layers_of(c, "sliding_attention") == 3
    assert w.layers_of(c, "full_attention") == 1
    assert w.kv_bytes_per_row(c, "sliding_attention") == 2 * 3 * 4 * 128 * 2
    assert w.kv_bytes_per_row(c, "full_attention") == 2 * 1 * 4 * 128 * 2


def test_mellum_serve_flops_clip_window_rows():
    w, c = _work("mellum2-12b-a2.5b"), MELLUM["config"]
    act, head = w.active_params(c), 2304 * 98_304
    full, win = 4 * 1 * 32 * 128, 4 * 3 * 32 * 128
    wnd = {"prompt_tokens": 2000, "admits": 1, "prompt_pairs": 2001000,
           "prompt_pairs_window": 1024 * 1025 // 2 + 976 * 1024,
           "decode_tokens": 3, "decode_rows_full": 6003,
           "decode_rows_window": 3072}
    assert w.serve_flops(c, wnd) == (
        2 * (act - head) * 2000 + 2 * head + full * 2001000
        + win * (1024 * 1025 // 2 + 976 * 1024) + 2 * act * 3
        + full * 6003 + win * 3072)
