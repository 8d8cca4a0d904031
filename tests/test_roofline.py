"""Unit tests: roofline derivation (HLO collective parsing, term math,
MODEL_FLOPS accounting)."""
import numpy as np

import pytest

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCHS
from repro.launch import roofline as roof

HLO = """
ENTRY %main {
  %p0 = bf16[8,128]{1,0} parameter(0)
  %ag = bf16[128,128]{1,0} all-gather(%p0), replica_groups={}
  %ar = f32[64,2]{1,0} all-reduce(%x), to_apply=%add
  %a2a = bf16[16,16]{1,0} all-to-all(%y), dimensions={0}
  %agsta = (bf16[32,4]{1,0}, bf16[32,4]{1,0}) all-gather-start(%z)
  %agdone = bf16[32,4]{1,0} all-gather-done(%agsta)
  %cp = u32[10]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %not_a_collective = f32[2,2]{1,0} add(%a, %b)
}
"""


def test_parse_collectives():
    out = roof.parse_collectives(HLO)
    assert out["all-gather"] == 128 * 128 * 2 + 2 * 32 * 4 * 2
    assert out["all-reduce"] == 64 * 2 * 4
    assert out["all-to-all"] == 16 * 16 * 2
    assert out["collective-permute"] == 10 * 4
    assert out["reduce-scatter"] == 0


def test_parse_skips_done_halves():
    # '-done' lines are skipped; '-start' counted once
    out = roof.parse_collectives(HLO)
    # only the start tuple contributed (2 x 32 x 4 x 2 bytes)
    assert out["all-gather"] - 128 * 128 * 2 == 512


def test_roofline_terms_and_dominance():
    pk = roof.peaks("TPU v5 lite")
    cost = {"flops": pk["flops_bf16"], "bytes accessed": pk["hbm_bw"] / 2}
    coll = {"all-gather": 0, "all-reduce": pk["ici_bw"], "reduce-scatter": 0,
            "all-to-all": 0, "collective-permute": 0}
    r = roof.roofline(cost, coll, "TPU v5 lite")
    assert r["compute_s"] == 1.0
    assert r["memory_s"] == 0.5
    assert r["collective_s"] == 2.0        # all-reduce counts 2x
    assert r["dominant"] == "collective_s"
    assert r["bound_s"] == 2.0


def test_roofline_refuses_unknown_device_kind():
    with pytest.raises(ValueError, match="no published peaks"):
        roof.roofline({"flops": 1.0}, {}, "cpu")


def test_model_flops_train_vs_decode():
    cfg = ARCHS["qwen2.5-14b"]
    n = 14_000_000_000
    tr = roof.model_flops(cfg, n, INPUT_SHAPES["train_4k"], "train")
    assert tr == 6.0 * n * 256 * 4096
    de = roof.model_flops(cfg, n, INPUT_SHAPES["decode_32k"], "decode")
    assert de == 2.0 * n * 128


def test_moe_active_params_discount():
    cfg = ARCHS["dbrx-132b"]
    n = 132_000_000_000
    act = roof.active_params(cfg, n)
    expert_w = 40 * 16 * 3 * cfg.d_model * cfg.d_ff
    assert act == n - expert_w + expert_w * 4 // 16
    assert act < n


def test_dense_active_params_identity():
    cfg = ARCHS["qwen2-72b"]
    assert roof.active_params(cfg, 123) == 123
