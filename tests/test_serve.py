"""Continuous-batching serving engine (repro/serve + paged kernels).

Covers the paged flash-decode kernel (parity vs the dense oracle across
page sizes, ragged last pages, GQA, inactive slots, the int8 fused
dequant path with documented error bounds), the slot scheduler (argsort
slot/page picks, the host ledger mirror, request validation), the
end-to-end engine (exact token accounting, page conservation under
churn, continuous == fixed == dense-full-cache parity under argmax,
max_new=1 completing at admission), and the serving telemetry artifacts
(measured round spans + Perfetto counter tracks, schema checks)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.paged_decode import paged_flash_decode
from repro.kernels.paged_decode_ref import (dequant_pool, gather_pages,
                                            paged_decode_ref)
from repro.launch.serve import draw_requests, make_decode_step
from repro.models.model import build
from repro.serve import (HostLedger, Request, ServeConfig, ServeEngine,
                         kv_bytes_read)
from repro.serve import scheduler as sched

# measured fp32 kernel-vs-oracle gap is ~2e-7; int8 kernel vs the int8
# oracle is exact modulo fp32 op order (~3e-7), while int8 vs fp32 is
# quantization error (~1.3e-2 for unit-normal K/V at qblk = head_dim)
FP32_ATOL = 1e-5
INT8_KERNEL_ATOL = 2e-5
INT8_QUANT_ATOL = 5e-2


def _rand_paged(seed, s, maxp, page, hq, hkv, dh, n_extra=3):
    """Random pool + table + ragged lengths (incl. one inactive slot)."""
    key = jax.random.PRNGKey(seed)
    n = s * maxp + n_extra
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (s, hq, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (n, hkv, page, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (n, hkv, page, dh), jnp.float32)
    table = jax.random.permutation(ks[3], n)[:s * maxp].reshape(s, maxp)
    # ragged: full pages, partial last page, single row, inactive (0)
    lengths = jax.random.randint(ks[4], (s,), 1, maxp * page + 1)
    lengths = lengths.at[0].set(maxp * page)       # every page full
    lengths = lengths.at[1].set(page + 1)          # ragged last page
    if s > 2:
        lengths = lengths.at[2].set(0)             # inactive slot
    return q, kp, vp, table.astype(jnp.int32), lengths.astype(jnp.int32)


def _pool_quant(pool):
    """int8 codes + (N, Hkv, 1, page) scales of an (N, Hkv, page, dh)
    pool, by the append-time quantizer the serving cache uses."""
    from repro.models.attention import _paged_quant
    codes, scale = _paged_quant(pool)
    return codes, scale[:, :, None, :]


class TestPagedKernel:
    @pytest.mark.parametrize("page,maxp", [(4, 6), (8, 3), (16, 2)])
    def test_parity_vs_ref_across_page_sizes(self, page, maxp):
        q, kp, vp, table, lengths = _rand_paged(page, 4, maxp, page,
                                                hq=4, hkv=2, dh=64)
        out = paged_flash_decode(q, kp, vp, table, lengths,
                                 interpret=True)
        ref = paged_decode_ref(q, kp, vp, table, lengths)
        np.testing.assert_allclose(out, ref, atol=FP32_ATOL)

    def test_parity_vs_plain_sdpa(self):
        page, maxp, s = 8, 4, 3
        q, kp, vp, table, lengths = _rand_paged(7, s, maxp, page,
                                                hq=4, hkv=2, dh=64)
        out = paged_flash_decode(q, kp, vp, table, lengths,
                                 interpret=True)
        k = gather_pages(kp, table)
        v = gather_pages(vp, table)
        g = 4 // 2
        for si in range(s):
            L = int(lengths[si])
            if L == 0:
                continue
            for h in range(4):
                qs = np.asarray(q[si, h]) / np.sqrt(64)
                logits = qs @ np.asarray(k[si, h // g, :L]).T
                p = np.exp(logits - logits.max())
                p /= p.sum()
                expect = p @ np.asarray(v[si, h // g, :L])
                np.testing.assert_allclose(out[si, h], expect,
                                           atol=FP32_ATOL)

    def test_inactive_slot_outputs_zero(self):
        q, kp, vp, table, lengths = _rand_paged(1, 4, 3, 8,
                                                hq=4, hkv=2, dh=64)
        out = paged_flash_decode(q, kp, vp, table, lengths,
                                 interpret=True)
        assert int(lengths[2]) == 0
        np.testing.assert_array_equal(np.asarray(out[2]), 0.0)

    def test_int8_kernel_matches_int8_oracle(self):
        q, kp, vp, table, lengths = _rand_paged(11, 4, 3, 8,
                                                hq=4, hkv=2, dh=64)
        kq, ksc = _pool_quant(kp)
        vq, vsc = _pool_quant(vp)
        out = paged_flash_decode(q, kq, vq, table, lengths,
                                 k_scale=ksc, v_scale=vsc,
                                 interpret=True)
        ref = paged_decode_ref(q, kq, vq, table, lengths,
                               k_scale=ksc, v_scale=vsc)
        np.testing.assert_allclose(out, ref, atol=INT8_KERNEL_ATOL)
        # dequant helper round-trips the codes the ref consumed
        np.testing.assert_allclose(dequant_pool(kq, ksc), kp,
                                   atol=INT8_QUANT_ATOL)

    def test_int8_vs_fp32_quantization_bound(self):
        q, kp, vp, table, lengths = _rand_paged(13, 4, 3, 8,
                                                hq=4, hkv=2, dh=64)
        kq, ksc = _pool_quant(kp)
        vq, vsc = _pool_quant(vp)
        out8 = paged_flash_decode(q, kq, vq, table, lengths,
                                  k_scale=ksc, v_scale=vsc,
                                  interpret=True)
        out32 = paged_decode_ref(q, kp, vp, table, lengths)
        err = float(jnp.max(jnp.abs(out8 - out32)))
        assert err < INT8_QUANT_ATOL, err


class TestScheduler:
    def test_pick_free_slot_first_inactive(self):
        active = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        slot, ok = sched.pick_free_slot(active)
        assert int(slot) == 1 and bool(ok)
        slot, ok = sched.pick_free_slot(jnp.ones((3,)))
        assert not bool(ok)

    def test_take_pages_and_infeasible(self):
        free = jnp.asarray([1.0, 0.0, 1.0, 1.0])
        pages, ok, free2 = sched.take_pages(free, jnp.int32(2), 3)
        assert bool(ok)
        assert sorted(np.asarray(pages)[:2].tolist()) == [0, 2]
        assert float(free2.sum()) == 1.0
        # infeasible: nothing taken
        _, ok, free3 = sched.take_pages(free2, jnp.int32(2), 3)
        assert not bool(ok)
        np.testing.assert_array_equal(np.asarray(free3),
                                      np.asarray(free2))

    def test_validate_request(self):
        scfg = ServeConfig(max_slots=2, page_size=4, max_len=16,
                           prompt_pad=8)
        sched.validate_request(Request(0, (1, 2, 3), 4), scfg)
        with pytest.raises(ValueError):
            sched.validate_request(Request(1, (), 4), scfg)
        with pytest.raises(ValueError):
            sched.validate_request(Request(2, tuple(range(9)), 4), scfg)
        with pytest.raises(ValueError):
            sched.validate_request(Request(3, (1,), 0), scfg)

    def test_host_ledger_mirror(self):
        scfg = ServeConfig(max_slots=2, page_size=4, max_len=16,
                           prompt_pad=4)
        led = HostLedger(scfg)
        assert led.can_admit(4) and led.next_slot() == 0
        led.admit_at(0, 4)
        assert led.next_slot() == 1 and led.free_pages == 4
        led.admit_at(1, 4)
        assert not led.can_admit(1)
        led.evict(0)
        assert led.next_slot() == 0 and led.free_pages == 4

    def test_kv_bytes_read_int8_reduction(self):
        cfg = get_config("tiny-lm").reduced()
        f32 = kv_bytes_read(cfg, ServeConfig(page_size=16), 4.0)
        i8 = kv_bytes_read(cfg, ServeConfig(page_size=16, kv_int8=True),
                           4.0)
        assert f32 / i8 > 3.0


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny-lm").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _scfg(**kw):
    base = dict(max_slots=4, page_size=8, max_len=48, prompt_pad=8,
                attn="ref")
    base.update(kw)
    return ServeConfig(**base)


class TestEngine:
    def test_churn_exact_token_counts_and_page_conservation(self, tiny):
        cfg, _, params = tiny
        scfg = _scfg()
        engine = ServeEngine(cfg, scfg, params, seed=2)
        reqs = draw_requests(10, 6, 2, 24, cfg.vocab_size, seed=5)
        results, stats = engine.run(reqs, continuous=True)
        for r in reqs:
            assert len(results[r.req_id]) == r.max_new, r
        assert stats["free_pages_end"] == scfg.total_pages
        assert stats["tokens"] == sum(r.max_new for r in reqs)

    def test_continuous_matches_fixed_tokens(self, tiny):
        cfg, _, params = tiny
        scfg = _scfg()
        reqs = draw_requests(6, 6, 2, 16, cfg.vocab_size, seed=9)
        engine = ServeEngine(cfg, scfg, params, seed=0)
        cont, s_cont = engine.run(reqs, continuous=True)
        fixed, s_fixed = engine.run(reqs, continuous=False)
        assert cont == fixed          # argmax: scheduling can't change tokens
        assert s_cont["steps"] <= s_fixed["steps"]

    def test_admit_order_independence_per_request(self, tiny):
        # a request's tokens depend on its prompt, not on its
        # companions' slot churn (argmax decoding)
        cfg, _, params = tiny
        scfg = _scfg()
        engine = ServeEngine(cfg, scfg, params, seed=0)
        reqs = draw_requests(6, 6, 2, 12, cfg.vocab_size, seed=4)
        a, _ = engine.run(reqs, continuous=True)
        b, _ = engine.run(list(reversed(reqs)), continuous=True)
        assert a == b

    def test_paged_matches_dense_full_cache(self, tiny):
        cfg, model, params = tiny
        plen, gen = 5, 8
        prompt = tuple(np.random.RandomState(3)
                       .randint(0, cfg.vocab_size, plen).tolist())
        engine = ServeEngine(cfg, _scfg(), params, seed=0)
        results, _ = engine.run([Request(0, prompt, gen)],
                                continuous=True)
        # dense oracle: full-cache prefill + greedy decode
        cache = model.init_cache(1, plen + gen, dtype=jnp.float32)
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, cache = jax.jit(model.prefill)(
            params, {"tokens": toks}, cache)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        step = jax.jit(make_decode_step(model, temperature=0.0))
        dense = [int(tok[0, 0])]
        key = jax.random.PRNGKey(0)
        for i in range(gen - 1):
            tok, cache, key = step(params, tok, cache,
                                   jnp.int32(plen + i), key)
            dense.append(int(tok[0, 0]))
        assert results[0] == dense

    def test_max_new_1_completes_at_admission(self, tiny):
        cfg, _, params = tiny
        scfg = _scfg()
        engine = ServeEngine(cfg, scfg, params, seed=0)
        results, stats = engine.run([Request(0, (1, 2, 3), 1)],
                                    continuous=True)
        assert len(results[0]) == 1
        assert stats["steps"] == 0
        assert stats["free_pages_end"] == scfg.total_pages

    def test_int8_engine_end_to_end(self, tiny):
        cfg, _, params = tiny
        engine = ServeEngine(cfg, _scfg(kv_int8=True), params, seed=0)
        reqs = draw_requests(4, 6, 2, 10, cfg.vocab_size, seed=1)
        results, stats = engine.run(reqs, continuous=True)
        for r in reqs:
            assert len(results[r.req_id]) == r.max_new
        assert stats["free_pages_end"] == engine.scfg.total_pages

    def test_pallas_engine_matches_ref_engine(self, tiny):
        cfg, _, params = tiny
        reqs = draw_requests(3, 6, 2, 8, cfg.vocab_size, seed=2)
        ref, _ = ServeEngine(cfg, _scfg(attn="ref"), params,
                             seed=0).run(reqs)
        pal, _ = ServeEngine(cfg, _scfg(attn="pallas"), params,
                             seed=0).run(reqs)
        assert ref == pal


class TestServeTelemetry:
    def test_trace_and_jsonl_artifacts(self, tiny, tmp_path):
        from repro import obs
        from repro.obs.check import check_jsonl, check_trace
        cfg, _, params = tiny
        trace_p = str(tmp_path / "trace.json")
        jsonl_p = str(tmp_path / "obs.jsonl")
        tel = obs.Telemetry(sinks=[obs.JsonlSink(jsonl_p)],
                            trace_path=trace_p, run_name="serve-test")
        engine = ServeEngine(cfg, _scfg(), params, seed=0)
        reqs = draw_requests(4, 6, 2, 10, cfg.vocab_size, seed=0)
        engine.run(reqs, telemetry=tel, continuous=True)
        tel.finish()
        assert check_trace(trace_p) == []
        assert check_jsonl(jsonl_p, require_obs=True,
                           engine="serve") == []
        with open(trace_p) as f:
            evs = json.load(f)["traceEvents"]
        rounds = [e for e in evs if e["name"] == "round"
                  and e["ph"] == "X"]
        counters = [e for e in evs if e.get("ph") == "C"]
        assert rounds, "no measured round spans"
        assert all("attributed" not in e.get("args", {})
                   for e in rounds)
        tracks = {e["name"] for e in counters}
        assert "serve/slot_occupancy" in tracks
        assert "serve/pages_in_use" in tracks

    def test_measured_wire_bytes_rows(self):
        from repro.launch.roofline import measured_wire_bytes
        rows = [{"obs/wire/bytes_up": 100.0, "obs/wire/bytes_down": 40.0},
                {"obs/wire/bytes_up": 50.0, "obs/wire/bytes_down": 20.0}]
        w = measured_wire_bytes(rows)
        assert w["rounds"] == 2
        assert w["bytes_up"] == 150.0
        assert w["bytes_up_per_round"] == 75.0
        assert w["bytes_down"] == 60.0
