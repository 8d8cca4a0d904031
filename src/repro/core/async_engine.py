"""Buffered-async round engine (ROADMAP item 1): population-scale
client scheduling with deadline/timeout semantics and graceful
degradation under client failure.

The synchronous SimEngine (core/fedfits.py) assumes the cohort IS the
population and every contributor answers inside the round.  This engine
models the cross-device regime (FedSelect-ME's multi-edge setting):

  population   M registered clients live in a sharded ClientStore
               (core/clientstore.py); each round samples a cohort of
               C = fed_cfg.n_clients rows by O(M) Gumbel-top-d over the
               store's fitness x trust priority (selection.
               population_cohort -> kernels/population_select.py) and
               gathers just those rows into the round.
  deadline     every cohort delivery races ``async_deadline`` with a
               heterogeneous exponential delay (core/faults.py: chronic
               stragglers at ``straggler_delay``, the rest at
               ``base_delay``).  On-time updates aggregate at full
               weight.
  buffer       a late update is NOT lost: it parks in a fixed-capacity
               DeliveryBuffer (B = C * async_max_retries rows) and
               retries on later rounds with CAPPED BACKOFF — the retry
               window of a row aged a is deadline * backoff^a, so each
               retry listens longer (FedBuff-style buffered async
               aggregation, generalizing the sync engine's
               ``stale_weight`` catch-up path).  When it finally lands
               it enters the aggregation at staleness-decayed weight
               n_k * trust * staleness_decay^a — fresh evidence
               dominates stale evidence, and the combination stays
               convex (``delivery_weights``).
  timeout      a row that exhausts ``async_max_retries`` (or arrives
               when the buffer is full) is ABANDONED: the work was done
               and is billed (billed-but-lost, exactly the PR-5 dropout
               semantics) but the bytes never help the model, and the
               client's failure count rises while its trust decays
               multiplicatively — the Gumbel-top-d priority shrinks and
               the scheduler routes around chronically flaky clients
               (graceful degradation).
  guard        every delivery (fresh or buffered) passes the
               aggregation-boundary guard (aggregation.sanitize_updates)
               — NaN/Inf or absurd-norm rows are rejected with a trust
               penalty instead of poisoning the global model.

Every draw (cohort sample, local-training keys, delivery delays) folds
off the round carry's rng, so the chunked ``lax.scan`` driver and the
per-round jitted python loop are bit-for-bit equal with the buffer,
retry/backoff, and fault injection all active (tests/test_async_engine).

Compression is deliberately NOT supported here: EF residuals are
per-client persistent state, and at M >> C they must live behind the
ClientStore boundary as (M, ...) columns (that is exactly why the sync
engine's ``ef`` moved into the store this PR); wiring the codec through
gather/scatter is future work, so ``compress != none`` raises.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import codecs as comm_codecs
from repro.core import aggregation, clientstore, driver as scan_driver, \
    fairness, faults as faults_mod, fitness
from repro.core.fedfits import scoped_eval
from repro.obs import counters as obs_counters
from repro.obs.trace import annotate as obs_annotate, span

_EPS = 1e-12


class DeliveryBuffer(NamedTuple):
    """Fixed-capacity parking lot for late deliveries (B rows)."""
    upd: Any                  # (B, ...) update rows (zeros when inactive)
    owner: jnp.ndarray        # (B,) i32 population row of the delivery
    n_k: jnp.ndarray          # (B,) f32 owner's example count (weight)
    age: jnp.ndarray          # (B,) i32 rounds spent buffered (>= 1)
    remaining: jnp.ndarray    # (B,) f32 delay left past consumed windows
    active: jnp.ndarray       # (B,) 0/1 occupancy


class AsyncState(NamedTuple):
    params: Any
    clients: clientstore.ClientStore   # (M,) population columns
    buf: DeliveryBuffer
    rng: jnp.ndarray
    round: jnp.ndarray
    cost_client_rounds: jnp.ndarray
    cost_bytes_up: jnp.ndarray
    cost_bytes_down: jnp.ndarray
    attacker: Any = None      # stateful-attacker carry (None = stateless)
    tele: Any = None          # telemetry carry column (repro/obs/):
                              # {counter name: f32 array}; None = obs off

    # summarize()-compat read paths (match FedState's properties)
    @property
    def trust(self):
        return self.clients.trust

    @property
    def gate_trust(self):
        return self.clients.gate_trust

    @property
    def cum_selected(self):
        return self.clients.cum_selected


def buffer_capacity(fed_cfg) -> int:
    """B = C * max_retries: every cohort row can be late every round and
    nothing is evicted before its retries run out."""
    return max(fed_cfg.n_clients * fed_cfg.async_max_retries, 1)


def init_buffer(params, fed_cfg) -> DeliveryBuffer:
    b = buffer_capacity(fed_cfg)
    upd = jax.tree_util.tree_map(
        lambda p: jnp.zeros((b,) + p.shape, p.dtype), params)
    return DeliveryBuffer(
        upd=upd,
        owner=jnp.zeros((b,), jnp.int32),
        n_k=jnp.zeros((b,), jnp.float32),
        age=jnp.zeros((b,), jnp.int32),
        remaining=jnp.zeros((b,), jnp.float32),
        active=jnp.zeros((b,), jnp.float32),
    )


def init_async_state(params, fed_cfg, rng, *, attacker=None) -> AsyncState:
    m = fed_cfg.population or fed_cfg.n_clients
    att = attacker.init(m) if attacker is not None else None
    return AsyncState(
        params=params,
        clients=clientstore.init_store(m),
        buf=init_buffer(params, fed_cfg),
        rng=rng,
        round=jnp.int32(1),
        cost_client_rounds=jnp.float32(0.0),
        cost_bytes_up=jnp.float32(0.0),
        cost_bytes_down=jnp.float32(0.0),
        attacker=att,
    )


def delivery_weights(n_k, trust, mask, age, *, staleness_decay):
    """The normalized aggregation weights of one async round: raw weight
    n_k * trust * staleness_decay^age per masked-in delivery, normalized
    over the round's delivery set.  Always a convex combination (entries
    in [0, 1] summing to 1 — or all-zero for an empty round), which is
    the property tests' invariant; the round body feeds the SAME raw
    weights through ``aggregation.aggregate`` (whose ``normalize_weights``
    applies the identical normalization)."""
    w = n_k * trust * staleness_decay ** age.astype(jnp.float32)
    return aggregation.normalize_weights(w, mask)


def make_async_round(model, fed_cfg, pop_data, *, batch_size=32,
                     eval_batch=32, data_attack=None, update_attack=None,
                     malicious=None, faults=None, straggler_rows="tail"):
    """Builds the jittable buffered-async round body.

    ``pop_data``: population-stacked {x: (M, cap, ...), y, eval_x,
    eval_y, n} living on device (data/pipeline.py ``Federation.data``).
    Per-round cohort batches are sampled INSIDE the body from the carry
    rng, so the scan and python drivers see identical draws.
    """
    from repro.core import fedfits   # cycle-free: fedfits doesn't import us

    if getattr(fed_cfg, "compress", "none") != "none":
        # FedConfig.__post_init__ already rejects population>0 +
        # compress; this guards duck-typed / hand-rolled configs too.
        raise ValueError(
            f"compress={fed_cfg.compress!r}: the buffered-async engine "
            "is dense-uplink only (EF residual columns must live behind "
            "the ClientStore boundary before a codec can ride the retry "
            "buffer). Use the sync engine (fedfits.run) for compressed "
            "uplink, or compress='none' here.")
    client_update = fedfits.make_client_update(model, fed_cfg)
    m = fed_cfg.population or fed_cfg.n_clients
    c = fed_cfg.n_clients
    retries = int(fed_cfg.async_max_retries)
    deadline = float(fed_cfg.async_deadline)
    backoff = float(fed_cfg.async_backoff)
    sdecay = float(fed_cfg.staleness_decay)
    guard_on = getattr(fed_cfg, "update_guard", True)
    stateful_attack = getattr(update_attack, "stateful", False)
    mal = malicious if malicious is not None else jnp.zeros((m,), jnp.float32)
    fl = faults if faults is not None else faults_mod.FaultConfig()
    # per-POPULATION-row chronic-straggler delay scales, fixed per run
    scales_pop = faults_mod.delay_scales(fl, m, rows=straggler_rows) \
        if fl.stragglers_active else jnp.zeros((m,), jnp.float32)
    cap = pop_data["x"].shape[1]
    ecap = pop_data["eval_x"].shape[1]
    bsz = min(batch_size, cap)
    esz = min(eval_batch, ecap)

    def round_fn(state: AsyncState, _batch):
        rng, r_sel, r_cli, r_data, r_upd, r_delay = \
            jax.random.split(state.rng, 6)
        t = state.round
        store = state.clients
        buf = state.buf

        # ---- O(M) cohort sampling + O(C) gather ------------------------
        with obs_annotate("selection"):
            idx = clientstore.select_cohort(
                store, c, r_sel, method=fed_cfg.select_method)
            store = clientstore.record_selection(store, idx)
            rows = jax.tree_util.tree_map(lambda a: a[idx], pop_data)
        kb, ke = jax.random.split(jax.random.fold_in(r_data, 3))
        bi = jax.random.randint(kb, (c, bsz), 0, cap)
        ei = jax.random.randint(ke, (c, esz), 0, ecap)
        take = lambda arr, i: jax.vmap(lambda a, j: a[j])(arr, i)
        cdata = {"x": take(rows["x"], bi), "y": take(rows["y"], bi),
                 "eval_x": take(rows["eval_x"], ei),
                 "eval_y": take(rows["eval_y"], ei), "n": rows["n"]}
        cmal = mal[idx]
        if data_attack is not None:
            cdata = dict(cdata)
            cdata.update(data_attack(cdata, cmal, r_data))

        # ---- local training (vmapped cohort) ---------------------------
        eff = jnp.full((c,), fed_cfg.local_epochs, jnp.int32)
        keys = jax.random.split(r_cli, c)
        with obs_annotate("client_update"):
            locals_, (gl, ga, ll, la) = jax.vmap(
                client_update, in_axes=(None, 0, 0, 0))(state.params,
                                                        cdata, keys, eff)
        updates = jax.tree_util.tree_map(
            lambda w_k, w: w_k - w[None], locals_, state.params)
        att_carry = state.attacker
        if update_attack is not None:
            with obs_annotate("update_attack"):
                if stateful_attack:
                    att_view = update_attack.gather(state.attacker, idx) \
                        if hasattr(update_attack, "gather") \
                        else state.attacker
                    updates, att_carry = update_attack(
                        updates, cmal, r_upd, att_view)
                else:
                    updates = update_attack(updates, cmal, r_upd)

        # ---- fitness at COMPUTE time (a late delivery does not
        # re-evaluate; its score was recorded when the work ran) ---------
        ones_c = jnp.ones((c,), jnp.float32)
        q = fitness.data_quality(cdata["n"], ones_c)
        th = jnp.where(t == 1, jnp.zeros((c,)),
                       fitness.theta(gl, ga, ll, la))
        alpha = jnp.where(
            jnp.array(fed_cfg.dynamic_alpha),
            fitness.dynamic_alpha(q, th, ones_c),
            jnp.float32(fed_cfg.alpha))
        scores = fitness.score(q, th, alpha)
        store = clientstore.record_fitness(store, idx, scores,
                                           fed_cfg.trust_decay)

        # ---- the delivery race -----------------------------------------
        with obs_annotate("delivery"):
            delay = faults_mod.sample_delays(
                scales_pop[idx], jax.random.fold_in(r_delay, 11)) \
                if fl.stragglers_active else jnp.zeros((c,), jnp.float32)
            on_time = (delay <= deadline).astype(jnp.float32)
            late = 1.0 - on_time

            # ---- buffer maturity: which parked rows land this round? ---
            # a row aged a listens for window = deadline * backoff^a
            # (capped backoff: a <= max_retries by construction); if its
            # residual delay fits, it is DUE and delivers at staleness-
            # decayed weight; if not and its retries are spent it is
            # ABANDONED (failure); otherwise it consumes the window and
            # ages one round.
            window = deadline * backoff ** buf.age.astype(jnp.float32)
            due = buf.active * (buf.remaining <= window).astype(jnp.float32)
            exhausted = buf.active * (1.0 - due) \
                * (buf.age >= retries).astype(jnp.float32)
            still = buf.active * (1.0 - due) * (1.0 - exhausted)

        # ---- staleness-weighted aggregation over fresh ∪ due -----------
        all_upd = jax.tree_util.tree_map(
            lambda u, b: jnp.concatenate([u, b], axis=0), updates, buf.upd)
        owners = jnp.concatenate([idx, buf.owner])
        owner_safe = jnp.clip(owners, 0, m - 1)
        age_all = jnp.concatenate(
            [jnp.zeros((c,), jnp.int32), buf.age])
        nk_all = jnp.concatenate([cdata["n"].astype(jnp.float32), buf.n_k])
        mask_pre = jnp.concatenate([on_time, due])
        w_raw = nk_all * store.trust[owner_safe] \
            * sdecay ** age_all.astype(jnp.float32)

        rejected = jnp.zeros_like(mask_pre)
        mask = mask_pre
        g_nonfinite = g_norm = jnp.float32(0.0)
        if guard_on:
            if state.tele is not None:
                # guard rejections split by kind — shares the guard's own
                # reductions (CSE), a pure readout
                nf, nr = aggregation.rejection_kinds(
                    all_upd, mask_pre, norm_mult=fed_cfg.guard_norm_mult)
                g_nonfinite, g_norm = nf.sum(), nr.sum()
            with obs_annotate("sanitize"):
                all_upd, mask, rejected = aggregation.sanitize_updates(
                    all_upd, mask_pre, norm_mult=fed_cfg.guard_norm_mult)
        with obs_annotate("aggregate"):
            agg = aggregation.aggregate(all_upd, w_raw, mask, fed_cfg)
        with obs_annotate("writeback"):
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), state.params, agg)

        # ---- cosine gate + trust bookkeeping ---------------------------
        cos = aggregation.cosine_to_ref(all_upd, agg)
        gated = ((cos < fed_cfg.cosine_outlier_thresh)
                 & (mask > 0)).astype(jnp.float32)
        bad = jnp.maximum(gated, rejected)
        if stateful_attack:
            # the attacker only observes its own cohort rows' outcome
            with obs_annotate("update_attack"):
                att_carry = update_attack.observe(
                    att_carry,
                    jnp.zeros((m,), jnp.float32).at[owner_safe].max(
                        bad * mask_pre))
        store = clientstore.record_gate_trust(
            store, owners, mask_pre, bad, fed_cfg.trust_decay)
        # aggregation-trust EWMA for the cohort (compute-time scores)
        old_tr = store.trust[idx]
        new_tr = fed_cfg.trust_decay * old_tr \
            + (1.0 - fed_cfg.trust_decay) * scores
        store = store._replace(trust=store.trust.at[idx].set(new_tr))
        store = clientstore.record_deliveries(
            store, owners, mask_pre * (1.0 - rejected))

        # ---- buffer update: free landed/abandoned rows, park the late -
        if retries > 0:
            rem_mid = jnp.where(still > 0, buf.remaining - window, 0.0)
            age_mid = jnp.where(still > 0, buf.age + 1, 0)
            free = 1.0 - still
            # j-th free slot, in slot order: free slots keep their index
            # as the sort key, occupied ones sort after every free one
            b = still.shape[0]
            slot_order = jnp.argsort(jnp.where(
                free > 0, jnp.arange(b), b + jnp.arange(b)))
            late_rank = (jnp.cumsum(late) - 1.0).astype(jnp.int32)
            n_free = free.sum()
            can_park = (late > 0) & (late_rank.astype(jnp.float32) < n_free)
            dest = jnp.where(
                can_park, slot_order[jnp.clip(late_rank, 0, b - 1)],
                b).astype(jnp.int32)               # b = out of range: drop
            new_buf = DeliveryBuffer(
                upd=jax.tree_util.tree_map(
                    lambda bl, u: bl.at[dest].set(
                        u.astype(bl.dtype), mode="drop"),
                    buf.upd, updates),
                owner=buf.owner.at[dest].set(idx, mode="drop"),
                n_k=buf.n_k.at[dest].set(
                    cdata["n"].astype(jnp.float32), mode="drop"),
                age=age_mid.at[dest].set(1, mode="drop"),
                remaining=rem_mid.at[dest].set(
                    delay - deadline, mode="drop"),
                active=still.at[dest].set(1.0, mode="drop"),
            )
            overflow = late * (1.0 - can_park.astype(jnp.float32))
        else:
            new_buf = buf                           # no retries: no buffer
            overflow = late

        # ---- chronic-failure routing -----------------------------------
        # abandoned retries, buffer overflow, and guard rejections all
        # count: failures bump + multiplicative trust decay shrink the
        # owner's selection priority, so the scheduler routes around it
        fail = jnp.maximum(jnp.concatenate([overflow, exhausted]), rejected)
        store = clientstore.record_failures(store, owners, fail)

        # ---- billing: once per COMPUTED round --------------------------
        # every cohort client trained and transmitted this round: C
        # client-rounds + C encoded-update uplinks + C model downlinks.
        # Retried deliveries are NOT re-billed when they land (the work
        # ran once), and abandoned/timed-out work stays billed — exactly
        # the PR-5 dropout billed-but-lost semantics.
        bytes_up_pc = comm_codecs.dense_bytes_per_client(updates)
        bytes_down_pc = comm_codecs.param_bytes(state.params)
        billed = jnp.float32(c)

        # ---- telemetry readout (repro/obs/) -----------------------------
        # pure readouts of values the round already produced; nothing
        # downstream reads them back, so on/off runs are bit-identical
        new_tele, obs_metrics = state.tele, {}
        if state.tele is not None:
            wm = w_raw * mask
            vals = {
                "gate/cosine_rejected": gated.sum(),
                "guard/nonfinite": g_nonfinite,
                "guard/norm": g_norm,
                "select/team_size": jnp.float32(c),
                "delivery/on_time": on_time.sum(),
                "delivery/late": late.sum(),
                "buffer/occupancy": new_buf.active.sum(),
                "buffer/parked": (late - overflow).sum(),
                "buffer/overflow": overflow.sum(),
                "buffer/exhausted": exhausted.sum(),
                "buffer/age_hist": obs_counters.age_histogram(
                    new_buf.age, new_buf.active, fed_cfg),
                "agg/fresh_mass": wm[:c].sum(),
                "agg/stale_mass": wm[c:].sum(),
                "cohort/trust_q": obs_counters.quantiles(new_tr),
                "cohort/gate_trust_q": obs_counters.quantiles(
                    store.gate_trust[idx]),
                "cohort/fitness_q": obs_counters.quantiles(scores),
                "wire/bytes_up": billed * bytes_up_pc,
                "wire/bytes_down": billed * bytes_down_pc,
            }
            new_tele = obs_counters.accumulate(state.tele, vals, "async")
            obs_metrics = obs_counters.metric_keys(vals)

        new_state = AsyncState(
            params=new_params, clients=store, buf=new_buf, rng=rng,
            round=t + 1,
            cost_client_rounds=state.cost_client_rounds + billed,
            cost_bytes_up=state.cost_bytes_up + billed * bytes_up_pc,
            cost_bytes_down=state.cost_bytes_down + billed * bytes_down_pc,
            attacker=att_carry, tele=new_tele)
        metrics = {
            "team_size": jnp.float32(c),
            "on_time_frac": on_time.mean(),
            "delivered": mask.sum(),
            "buffered": (late - overflow).sum(),
            "buf_fill": new_buf.active.sum(),
            "abandoned": exhausted.sum() + overflow.sum(),
            "guard_rejected": rejected.sum(),
            "gated_frac": gated.sum() / jnp.maximum(mask_pre.sum(), 1.0),
            "gate_trust": store.gate_trust,
            "score": scores, "alpha": alpha,
            "global_loss_mean": gl.mean(), "local_loss_mean": ll.mean(),
            **fairness.round_fairness(ga, ones_c, store.cum_selected),
            **obs_metrics,
        }
        return new_state, metrics

    return round_fn


def run_async(model, fed_cfg, pop_data, n_rounds, rng, *, eval_fn=None,
              batch_size=32, eval_batch=32, data_attack=None,
              update_attack=None, malicious=None, faults=None,
              straggler_rows="tail", driver="scan", chunk_rounds=4,
              telemetry=None):
    """Drive ``n_rounds`` buffered-async rounds; returns (state, history).

    Mirrors ``fedfits.run``: driver="scan" goes through the shared
    chunked-scan driver, driver="python" is the per-round jitted loop
    kept for bit-parity testing — both consume identical carry-rng
    streams, and the batch feed is empty (population data is closed
    over; every draw lives in the carry)."""
    r_init, r_run = jax.random.split(rng)
    params = model.init(r_init)
    att = update_attack if getattr(update_attack, "stateful", False) \
        else None
    state = init_async_state(params, fed_cfg, r_run, attacker=att)
    eval_fn = scoped_eval(eval_fn)
    if telemetry is not None:
        telemetry.bind_engine("async")
        if telemetry.counters:
            state = state._replace(
                tele=obs_counters.init_column("async", fed_cfg))
    round_fn = make_async_round(
        model, fed_cfg, pop_data, batch_size=batch_size,
        eval_batch=eval_batch, data_attack=data_attack,
        update_attack=update_attack, malicious=malicious, faults=faults,
        straggler_rows=straggler_rows)

    if driver == "python":
        round_jit = jax.jit(round_fn)
        eval_jit = jax.jit(eval_fn) if eval_fn is not None else None
        rec = getattr(telemetry, "tracer", None)
        history = []
        for t in range(1, n_rounds + 1):
            w0 = telemetry.now_us() if telemetry is not None else 0.0
            # device_get syncs every round under this driver, so the
            # span measures the whole round
            with span("round", rec, round=t):
                state, metrics = round_jit(state, {})
                row = {k: jax.device_get(v) for k, v in metrics.items()}
                if eval_jit is not None:
                    row.update(jax.device_get(eval_jit(state.params)))
            row["round"] = t
            if telemetry is not None:
                telemetry.observe_rows([row], w0, telemetry.now_us() - w0)
            history.append(row)
        return state, history
    if driver != "scan":
        raise ValueError(driver)

    def body(st, xs):
        _t, batch = xs
        st, metrics = round_fn(st, batch)
        if eval_fn is not None:
            metrics = {**metrics, **eval_fn(st.params)}
        return st, metrics

    return scan_driver.run_chunked(
        body, state, lambda t: {}, n_rounds, chunk_steps=chunk_rounds,
        t0=1, index_key="round", telemetry=telemetry)
