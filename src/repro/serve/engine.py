"""Continuous-batching serving engine: paged KV + slotted decode.

One jitted **decode step** (donated cache pools + slot state) runs the
whole fleet of slots forever; one jitted **admit step** prefills a
request into a freshly allocated page run and samples its first token.
The host loop between steps is pure bookkeeping: drain the step's small
output dict, attribute tokens to requests, admit from the pending queue
while the :class:`~repro.serve.scheduler.HostLedger` says a slot + pages
are free.

Cache layout: the engine's master cache holds ONLY the page pools
(``kp``/``vp`` and the int8 ``ks``/``vs`` scales), stacked with the
transformer's n_units-leading layer scan axis.  The scheduler context
(page table, lengths, active mask) lives in :class:`SlotState` and is
broadcast into the per-call cache view (``_with_ctx``) — so the donated
pools alias in place while the tiny context rides the slot carry.

Two page budgets: full-attention layers share one pool through the
scheduler's page tables (full reservation at admission); window layers
hold a static ring of ``attention.ring_pages`` pages per slot, reused in
place, which needs no allocator traffic.

``run(requests, continuous=False)`` is the fixed-batch baseline for the
BENCH comparison: identical admit/decode programs, but admission only
happens when every slot is empty (classic batch-until-slowest-finishes
serving).  Scheduling is therefore the only variable between the two
rows.
"""
from __future__ import annotations

import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import transformer
from repro.obs import counters as obs_counters
from repro.obs.trace import span
from repro.serve import scheduler as sched
from repro.serve.scheduler import (HostLedger, Request, ServeConfig,
                                   SlotState)

POOL_KEYS = ("kp", "vp", "ks", "vs")


def init_paged_cache(cfg, scfg: ServeConfig):
    """Stacked page pools for the layer scan (pools only — the
    scheduler context is injected per call by _with_ctx): the shared
    pool on full-attention blocks, per-slot rings on window blocks."""
    cycle, n_units = transformer.layer_cycle(cfg)
    if any(k not in ("attn", "moe") for k in cycle):
        raise ValueError(
            "paged serving supports attn/moe stacks, got "
            f"{cycle}")
    unit = {}
    for i, window in enumerate(transformer.unit_windows(cfg)):
        one = attn_lib.init_paged_kv_cache(
            cfg, scfg.max_slots, scfg.total_pages, scfg.page_size,
            scfg.pages_per_slot, int8=scfg.kv_int8, dtype=jnp.float32,
            window=window)
        unit[f"b{i}"] = {k: v for k, v in one.items() if k in POOL_KEYS}
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_units,) + x.shape), unit)


def _with_ctx(pools, windows, table, length, active, new_valid, slot):
    """Cache view for one forward call: pools + scheduler context
    replicated across the stacked layer units.  Full-attention blocks
    read the page table, window blocks the slot of each row."""
    out = {}
    for name, block in pools.items():
        ring = windows[int(name[1:])] > 0
        ctx = {"slot" if ring else "table": slot if ring else table,
               "length": length, "active": active, "new_valid": new_valid}
        n_units = block["kp"].shape[0]
        b = dict(block)
        for k, v in ctx.items():
            b[k] = jnp.broadcast_to(v[None], (n_units,) + v.shape)
        out[name] = b
    return out


def _strip_ctx(cache):
    """Master cache back out of a forward's returned cache: pools only
    (the context echo is stale by design — SlotState owns it)."""
    return {name: {k: v for k, v in block.items() if k in POOL_KEYS}
            for name, block in cache.items()}


def kv_bytes_read(cfg, scfg: ServeConfig, pages_in_use: float) -> float:
    """KV bytes one decode step streams from the pools (all layers):
    live pages x rows x heads x head-dim x itemsize x {k, v}, plus the
    f32 scale planes on the int8 path; a window layer reads at most its
    slots' rings.  This is the measured-bytes mirror of the BENCH serve
    rows."""
    _, n_units = transformer.layer_cycle(cfg)
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    item = 1 if scfg.kv_int8 else 4
    row_bytes = 2.0 * hkv * (dh * item + (4 if scfg.kv_int8 else 0))
    pages = 0.0
    for window in transformer.unit_windows(cfg):
        pages += pages_in_use if not window else min(
            pages_in_use, scfg.max_slots * attn_lib.ring_pages(
                window, scfg.page_size, scfg.pages_per_slot))
    return row_bytes * scfg.page_size * pages * n_units


def kv_rows(windows, length, active):
    """The decode step's KV rows attended per layer of each kind, summed
    over active slots: ``serve/kv_rows_full`` (every row of the slot,
    with the one appended this step) and ``serve/kv_rows_window`` (the
    last ``window`` of them); 0 for a kind the model does not have."""
    n_keys = jnp.where(active > 0, length + 1, 0)
    window = max(windows)
    full = n_keys.sum() if 0 in windows else 0
    win = jnp.minimum(n_keys, window).sum() if window else 0
    return {"serve/kv_rows_full": jnp.asarray(full, jnp.float32),
            "serve/kv_rows_window": jnp.asarray(win, jnp.float32)}


class ServeEngine:
    """Builds the jitted admit/decode programs and drives the loop."""

    def __init__(self, cfg, scfg: ServeConfig, params, *, seed: int = 0):
        self.cfg = cfg.replace(
            attn_impl="pallas" if scfg.attn == "pallas" else "xla")
        self.scfg = scfg
        self.params = params
        self.seed = seed
        self._decode = jax.jit(self._make_decode(), donate_argnums=(1, 2))
        self._admit = jax.jit(self._make_admit(), donate_argnums=(1, 2))

    # -- state ---------------------------------------------------------
    def fresh_state(self) -> Tuple[dict, SlotState]:
        cache = init_paged_cache(self.cfg, self.scfg)
        st = sched.init_slot_state(
            self.scfg, jax.random.PRNGKey(self.seed),
            obs_counters.init_column("serve", None))
        return cache, st

    # -- jitted decode step -------------------------------------------
    def _make_decode(self):
        cfg, scfg = self.cfg, self.scfg
        s, n, maxp = scfg.max_slots, scfg.total_pages, scfg.pages_per_slot
        windows = transformer.unit_windows(cfg)

        def decode(params, pools, st: SlotState):
            key, sub = jax.random.split(st.key)
            view = _with_ctx(pools, windows, st.table, st.length, st.active,
                             jnp.zeros((s,), jnp.int32),
                             jnp.arange(s, dtype=jnp.int32))
            logits, new_cache, _ = transformer.forward(
                params, cfg, tokens=st.tok,
                positions=st.length[:, None], cache=view)
            lg = logits[:, 0]
            if scfg.temperature > 0:
                nxt = jax.random.categorical(sub, lg / scfg.temperature)
            else:
                nxt = jnp.argmax(lg, -1)
            nxt = nxt.astype(jnp.int32)
            act = st.active
            emitted = act
            new_len = st.length + (act > 0).astype(jnp.int32)
            done = (act > 0) & ((new_len >= st.budget)
                                | (nxt == scfg.eos_id))
            done_f = done.astype(jnp.float32)
            owned = (jnp.arange(maxp)[None, :] < st.alloc[:, None]) \
                & done[:, None]
            dest = jnp.where(owned, st.table, n).reshape(-1)
            free = st.free.at[dest].set(1.0, mode="drop")
            new_active = act * (1.0 - done_f)
            vals = {
                "serve/slot_occupancy": new_active.sum(),
                "serve/admitted": jnp.float32(0.0),
                "serve/evicted": done_f.sum(),
                "serve/tokens": act.sum(),
                "serve/pages_in_use": n - free.sum(),
                "serve/tokens_per_s": jnp.float32(0.0),
                **kv_rows(windows, st.length, act),
            }
            st2 = st._replace(
                tok=nxt[:, None], length=new_len, active=new_active,
                alloc=jnp.where(done, 0, st.alloc), free=free,
                tele=obs_counters.accumulate(st.tele, vals, "serve"),
                key=key)
            out = {"next": nxt, "emitted": emitted, "finished": done_f,
                   "req": st.req_id, "vals": vals}
            return _strip_ctx(new_cache), st2, out

        return decode

    # -- jitted admit step --------------------------------------------
    def _make_admit(self):
        cfg, scfg = self.cfg, self.scfg
        s, n, maxp = scfg.max_slots, scfg.total_pages, scfg.pages_per_slot
        pmax = scfg.prompt_pad
        windows = transformer.unit_windows(cfg)

        def admit(params, pools, st: SlotState, prompt, plen, max_new,
                  req_id):
            key, sub = jax.random.split(st.key)
            slot, has_slot = sched.pick_free_slot(st.active)
            budget = jnp.minimum(plen + max_new - 1, scfg.max_len)
            need = (budget + scfg.page_size - 1) // scfg.page_size
            pages, fits, free2 = sched.take_pages(st.free, need, maxp)
            ok = has_slot & fits
            live = ok & (max_new >= 2)
            # a max_new=1 request completes at admission: its transient
            # pages go straight back (stale rows are safe — appends
            # overwrite before any mask exposes them)
            free3 = jnp.where(live, free2, st.free)
            row = jnp.where(ok, pages, 0)
            view = _with_ctx(pools, windows, row[None],
                             jnp.zeros((1,), jnp.int32),
                             jnp.ones((1,), jnp.float32),
                             jnp.where(ok, plen, 0)[None], slot[None])
            hidden, new_cache, _ = transformer.forward(
                params, cfg, tokens=prompt[None],
                positions=jnp.arange(pmax)[None], cache=view,
                collect_logits=False)
            h = jnp.take(hidden[0], plen - 1, axis=0)
            lg = transformer.lm_head(params, cfg, h[None, None])[0, 0]
            if scfg.temperature > 0:
                tok0 = jax.random.categorical(sub, lg / scfg.temperature)
            else:
                tok0 = jnp.argmax(lg, -1)
            tok0 = tok0.astype(jnp.int32)
            sl = jnp.where(ok, slot, s)            # s = drop row
            live_f = live.astype(jnp.float32)
            active2 = st.active.at[sl].set(live_f, mode="drop")
            vals = {
                "serve/slot_occupancy": active2.sum(),
                "serve/admitted": ok.astype(jnp.float32),
                "serve/evicted": ok.astype(jnp.float32) * (1.0 - live_f),
                "serve/tokens": ok.astype(jnp.float32),
                "serve/pages_in_use": n - free3.sum(),
                "serve/tokens_per_s": jnp.float32(0.0),
                "serve/kv_rows_full": jnp.float32(0.0),
                "serve/kv_rows_window": jnp.float32(0.0),
            }
            st2 = st._replace(
                tok=st.tok.at[sl].set(tok0[None], mode="drop"),
                length=st.length.at[sl].set(plen, mode="drop"),
                budget=st.budget.at[sl].set(budget, mode="drop"),
                active=active2,
                req_id=st.req_id.at[sl].set(req_id, mode="drop"),
                alloc=st.alloc.at[sl].set(jnp.where(live, need, 0),
                                          mode="drop"),
                table=st.table.at[sl].set(row, mode="drop"),
                free=free3,
                tele=obs_counters.accumulate(st.tele, vals, "serve"),
                key=key)
            out = {"ok": ok, "slot": slot, "tok0": tok0, "vals": vals}
            return _strip_ctx(new_cache), st2, out

        return admit

    # -- host loop -----------------------------------------------------
    def run(self, requests: Sequence[Request], *, telemetry=None,
            continuous: bool = True) -> Tuple[Dict[int, List[int]], dict]:
        """Serve ``requests``; returns ({req_id: tokens}, stats).

        continuous=True: admit whenever a slot + pages free up (the
        tentpole path).  continuous=False: fixed-batch baseline — admit
        only into an all-empty fleet, then decode until every slot
        drains (identical compiled programs, scheduling is the only
        difference)."""
        scfg = self.scfg
        for r in requests:
            sched.validate_request(r, scfg)
        if telemetry is not None:
            telemetry.bind_engine("serve")
        ledger = HostLedger(scfg)
        pending = list(requests)
        cache, st = self.fresh_state()
        results: Dict[int, List[int]] = {r.req_id: [] for r in requests}
        occupancy_trail: List[int] = []
        steps = 0
        total_emitted = 0
        admitted_since = 0
        rec = getattr(telemetry, "tracer", None)
        t0 = time.perf_counter()
        while pending or ledger.n_active > 0:
            group_open = ledger.n_active == 0
            while pending:
                r = pending[0]
                need = sched.pages_needed(len(r.tokens), r.max_new, scfg)
                if not ledger.can_admit(need):
                    break
                if not continuous and not group_open:
                    break
                pending.pop(0)
                with span("serve.admit", rec, req_id=r.req_id):
                    want_slot = ledger.next_slot()
                    prompt = jnp.zeros((scfg.prompt_pad,), jnp.int32) \
                        .at[:len(r.tokens)].set(jnp.asarray(r.tokens,
                                                            jnp.int32))
                    cache, st, out = self._admit(
                        self.params, cache, st, prompt,
                        jnp.int32(len(r.tokens)), jnp.int32(r.max_new),
                        jnp.int32(r.req_id))
                    out = jax.device_get(out)
                    if not bool(out["ok"]) or int(out["slot"]) != want_slot:
                        raise RuntimeError(
                            f"scheduler mirror diverged on req {r.req_id}: "
                            f"device ok={bool(out['ok'])} "
                            f"slot={int(out['slot'])}, host slot={want_slot}")
                    results[r.req_id].append(int(out["tok0"]))
                    total_emitted += 1
                    admitted_since += 1
                    if r.max_new >= 2:
                        ledger.admit_at(want_slot, need)
            if ledger.n_active == 0:
                if pending:
                    raise RuntimeError("scheduler stalled with pending "
                                       "requests (pool too small?)")
                break
            w0 = telemetry.now_us() if telemetry is not None else 0.0
            with span("serve.decode", rec, step=steps + 1):
                ts = time.perf_counter()
                cache, st, out = self._decode(self.params, cache, st)
                out = jax.device_get(out)
                dt = time.perf_counter() - ts
            steps += 1
            with span("serve.bookkeep", rec, step=steps):
                ntok = 0
                for i in range(scfg.max_slots):
                    if out["emitted"][i] > 0:
                        results[int(out["req"][i])].append(
                            int(out["next"][i]))
                        ntok += 1
                    if out["finished"][i] > 0:
                        ledger.evict(i)
                total_emitted += ntok
                occupancy_trail.append(
                    int(out["vals"]["serve/slot_occupancy"]))
                if telemetry is not None:
                    row = {"round": steps}
                    row.update({obs_counters.METRIC_PREFIX + k: float(v)
                                for k, v in out["vals"].items()})
                    row[obs_counters.METRIC_PREFIX + "serve/admitted"] = \
                        float(admitted_since)
                    row[obs_counters.METRIC_PREFIX
                        + "serve/tokens_per_s"] = ntok / max(dt, 1e-9)
                    telemetry.observe_rows([row], w0,
                                           telemetry.now_us() - w0)
                admitted_since = 0
        wall = time.perf_counter() - t0
        stats = {
            "engine": "continuous" if continuous else "fixed",
            "steps": steps,
            "tokens": total_emitted,
            "wall_s": wall,
            "tokens_per_s": total_emitted / max(wall, 1e-9),
            "occupancy_trail": occupancy_trail,
            "free_pages_end": ledger.free_pages,
        }
        return results, stats
