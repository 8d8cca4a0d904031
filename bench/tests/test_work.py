"""Work functions and peaks against hand counts and the program's own
parameter trees (``jax.eval_shape``, nothing allocated)."""
import json
import math

import jax
import pytest

from bench import common, peaks

CNN = json.loads((common.ROOT / "bench/configs/paper-cnn.json").read_text())
GRANITE = json.loads(
    (common.ROOT / "bench/configs/granite-moe-1b-a400m.json").read_text())
ATTACK = json.loads(
    (common.ROOT / "bench/traffic/xray_attack_int8.json").read_text())


def _work(name):
    return common.load_module(common.ROOT / "bench" / "work" / f"{name}.py")


def _program_params(config):
    from repro.models.model import build

    cfg = common.program_config(config)
    tree = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    return sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(tree))


def test_cnn_params_match_program():
    w, c = _work("paper-cnn"), CNN["config"]
    assert w.n_params(c) == 421_642 == _program_params(CNN)
    assert w.leaf_sizes(c) == [288, 32, 18_432, 64, 401_408, 128, 1_280, 10]


def test_cnn_flops_hand_count():
    w, c = _work("paper-cnn"), CNN["config"]
    conv1 = 2 * 14 * 14 * 32 * 9 * 1
    conv2 = 2 * 7 * 7 * 64 * 9 * 32
    dense = 2 * 3136 * 128
    head = 2 * 128 * 10
    fwd = conv1 + conv2 + dense + head
    assert w.forward_flops(c) == fwd == 2_724_608
    # 10 clients x 2 epochs x 32 images x (fwd + bwd); 10 x 2 fitness evals
    # of 32; the server's test and trigger sets of 512
    assert w.round_flops(c, ATTACK) == (10 * 2 * 32 * 3 + 10 * 2 * 32
                                        + 2 * 512) * fwd


def test_cnn_aggregation_least_bytes():
    w, c = _work("paper-cnn"), CNN["config"]
    blocks = 3 + 1 + 144 + 1 + 3136 + 1 + 10 + 1
    assert w.agg_least_bytes(c, ATTACK) == (10 * 421_642 + 4 * 10 * blocks
                                            + 4 * 421_642)


def test_granite_params_match_program():
    w, c = _work("granite-moe-1b-a400m"), GRANITE["config"]
    assert w.total_params(c, padded_vocab=49_280) == 1_334_756_352 \
        == _program_params(GRANITE)


def test_granite_active_params_and_kv_hand_count():
    w, c = _work("granite-moe-1b-a400m"), GRANITE["config"]
    attn = 2 * 1024 * 1024 + 2 * 1024 * 512
    per_layer = attn + 1024 * 32 + 8 * 3 * 1024 * 512
    assert w.active_params(c) == 24 * per_layer + 1024 * 49_155
    assert w.kv_bytes_per_row(c) == 2 * 24 * 8 * 64 * 2 == 49_152


def test_granite_serve_flops():
    w, c = _work("granite-moe-1b-a400m"), GRANITE["config"]
    act, head = w.active_params(c), 1024 * 49_155
    per_key = 4 * 24 * 16 * 64
    win = {"prompt_tokens": 10, "admits": 1, "prompt_pairs": 55,
           "decode_tokens": 3, "decode_rows": 33}
    assert w.serve_flops(c, win) == (2 * (act - head) * 10 + 2 * head
                                     + per_key * 55 + 2 * act * 3
                                     + per_key * 33)


def test_peaks_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
