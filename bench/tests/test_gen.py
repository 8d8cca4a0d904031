"""The input generators: functions of the seed alone, and the serving mix's
sizes the same for every seed."""
import json

import numpy as np

from bench import common, gen

LONG = json.loads((common.ROOT / "bench/traffic/doc_long.json").read_text())


def test_requests_repeat_for_a_seed():
    a = gen.requests(2 ** 40 + 7, LONG["mix"], 49_155)
    b = gen.requests(2 ** 40 + 7, LONG["mix"], 49_155)
    assert a == b


def test_every_seed_gets_the_same_sizes_per_block():
    mix = LONG["mix"]
    blk = mix["block"]
    sizes = []
    for seed in (1, 2 ** 33 + 5):
        r = gen.requests(seed, mix, 49_155)
        assert len(r) == blk * mix["n_blocks"]
        per_block = [(sorted(len(p) for p, _ in r[i:i + blk]),
                      sorted(n for _, n in r[i:i + blk]))
                     for i in range(mix["warm"], len(r), blk)]
        sizes.append(per_block)
        assert all(b == per_block[0] for b in per_block)
        lo, hi = mix["prompt"]
        assert all(lo <= len(p) <= hi for p, _ in r)
    assert sizes[0] == sizes[1]


def test_warm_start_spreads_the_first_outputs():
    r = gen.requests(3, LONG["mix"], 49_155)
    first = sorted(n for _, n in r[:LONG["mix"]["warm"]])
    assert first[0] >= 2 and first == sorted(set(first))


def test_key_seed_takes_large_seeds():
    s = {gen.key_seed(x) for x in (0, 1, 2 ** 31, 2 ** 31 + 1, 2 ** 63 + 9)}
    assert len(s) == 5 and all(0 <= v < 2 ** 31 for v in s)


def test_federation_shapes():
    fed = {"n": 200, "holdout": 64, "n_classes": 2, "sep": 0.6,
           "dirichlet_alpha": 1.0, "n_clients": 4, "batch": 8,
           "eval_batch": 8}
    f = gen.Federation(5, fed)
    import jax
    b = f.data_fn(1, jax.random.PRNGKey(0))
    assert b["x"].shape == (4, 8, 28, 28, 1) and b["eval_y"].shape == (4, 8)
    assert f.server_test["x"].shape == (64, 28, 28, 1)
    assert float(np.sum(b["n"])) <= 200
