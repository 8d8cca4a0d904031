"""FedFiTS simulation engine — the paper-faithful Algorithm 1 + 2.

Per-client model replicas via ``vmap`` (cross-silo semantics: E local SGD
epochs per round from the global model, fitness evaluation on a client-local
test split, threshold election, slotted teams, trust-aware robust
aggregation). This engine drives the paper's experiments (EXPERIMENTS.md
§Paper-faithful) at the paper's own model scale; the pod-scale SPMD mapping
for the big architectures lives in core/pod.py.

Simulation note: every available client is *computed* each round (vmap is
SPMD-uniform), but only clients Algorithm 1 would actually train are
counted in the communication/compute cost metrics — `cost_client_rounds`
matches the paper's accounting (FFA rounds bill all clients, slot rounds
bill only the team).

Transport: with `FedConfig.compress` the client->server boundary runs
through the comm subsystem (repro/comm/) — updates cross the wire
encoded (EF residuals in the scan carry), the int8 path aggregates
straight from the codes (``aggregation.aggregate(..., enc=)``), and
`cost_bytes_up/down` bill the MEASURED wire sizes instead of an
analytic 2*|params|*4 model.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import codecs as comm_codecs, error_feedback
from repro.core import aggregation, attacks, clientstore, \
    driver as scan_driver, fairness, faults as faults_mod, fitness, \
    selection, slots
from repro.obs import counters as obs_counters
from repro.obs.trace import annotate as obs_annotate, span


class FedState(NamedTuple):
    """Round carry of the synchronous engine.  Per-client persistent
    columns (trust tracks, cum_selected, EF residuals, staleness,
    failure counts) live in the nested ``clients`` ClientStore — the
    sync engine is the M == K special case of the population-scale
    store (core/clientstore.py); back-compat properties keep the old
    ``state.trust`` / ``state.gate_trust`` / ``state.cum_selected`` /
    ``state.ef`` read paths working."""
    params: Any               # global model w(t-1)
    team: jnp.ndarray         # (K,) 0/1 mask S_t
    alpha: jnp.ndarray        # current alpha (dynamic or fixed)
    slot: slots.SlotState
    h: jnp.ndarray            # h(t): reselect this round?
    rng: jnp.ndarray
    round: jnp.ndarray        # t (1-indexed)
    cost_client_rounds: jnp.ndarray  # billed client-rounds (cost model)
    cost_bytes_up: jnp.ndarray    # MEASURED uplink bytes (encoded sizes)
    cost_bytes_down: jnp.ndarray  # MEASURED downlink bytes (dense model)
    clients: clientstore.ClientStore = None  # (K,) per-client columns
    attacker: Any = None      # stateful-attacker carry (cross-round
                              # adaptive attacks read last round's gate
                              # outcome from here; None = stateless)
    tele: Any = None          # telemetry carry column (repro/obs/):
                              # {counter name: f32 array}; None = obs off
                              # (the round body branches statically, so
                              # off-runs trace the exact pre-obs program)

    @property
    def trust(self):
        return self.clients.trust

    @property
    def gate_trust(self):
        return self.clients.gate_trust

    @property
    def cum_selected(self):
        return self.clients.cum_selected

    @property
    def ef(self):
        return self.clients.ef


def init_state(params, n_clients, fed_cfg, rng, *, attacker=None):
    store = clientstore.init_store(n_clients, params=params,
                                   fed_cfg=fed_cfg)
    att = attacker.init(n_clients) if attacker is not None else None
    return FedState(
        params=params,
        team=jnp.ones((n_clients,), jnp.float32),
        alpha=jnp.float32(fed_cfg.alpha),
        slot=slots.init_slot_state(),
        h=jnp.array(True),
        rng=rng,
        round=jnp.int32(1),
        cost_client_rounds=jnp.float32(0.0),
        cost_bytes_up=jnp.float32(0.0),
        cost_bytes_down=jnp.float32(0.0),
        clients=store,
        attacker=att,
    )


def make_client_update(model, fed_cfg):
    """Algorithm 2: E local epochs of SGD from w(t-1); returns the new local
    params and (GL, GA, LL, LA) evaluated on the client's test split.

    ``n_epochs`` (i32 scalar per vmapped client) is the client's EFFECTIVE
    epoch count (partial-work fault injection, core/faults.py): epochs past
    it still compute their gradient (the vmapped step stays SPMD-uniform,
    same as the availability simulation) but stop updating the parameters.
    With ``n_epochs == local_epochs`` the masking is the identity."""

    def client_update(params, data, rng, n_epochs):
        # data: {x, y, eval_x, eval_y, n} for ONE client
        def epoch(p, inp):
            _, i = inp

            def loss_fn(q):
                l, _ = model.loss(q, {"x": data["x"], "y": data["y"]})
                if fed_cfg.prox_mu:
                    # FedProx proximal term ||q - w(t-1)||^2 (Li et al.)
                    prox = sum(jnp.sum(jnp.square(a - b)) for a, b in zip(
                        jax.tree_util.tree_leaves(q),
                        jax.tree_util.tree_leaves(params)))
                    l = l + 0.5 * fed_cfg.prox_mu * prox
                return l

            g = jax.grad(loss_fn)(p)
            return jax.tree_util.tree_map(
                lambda w, gw: jnp.where(i < n_epochs,
                                        w - fed_cfg.local_lr * gw, w),
                p, g), None

        local, _ = jax.lax.scan(
            epoch, params,
            (jax.random.split(rng, fed_cfg.local_epochs),
             jnp.arange(fed_cfg.local_epochs)))

        gl, gmet = model.loss(params, {"x": data["eval_x"], "y": data["eval_y"]})
        ll, lmet = model.loss(local, {"x": data["eval_x"], "y": data["eval_y"]})
        return local, (gl, gmet["acc"], ll, lmet["acc"])

    return client_update


def make_round(model, fed_cfg, *, data_attack=None, update_attack=None,
               malicious=None, faults=None):
    """Builds the jittable one-round function.

    data_attack(batch_k_stacked, malicious, rng) -> corrupted batch
    update_attack(updates, malicious, rng) -> corrupted updates
    faults: optional ``faults.FaultConfig`` — system-heterogeneity
    injection (stragglers / mid-round dropout / partial local work).
    Fault draws come from keys folded off the round's own rng streams,
    so they live in the scan carry and scan==python parity holds.
    """
    client_update = make_client_update(model, fed_cfg)
    K = fed_cfg.n_clients
    mal = malicious if malicious is not None else jnp.zeros((K,), jnp.float32)
    codec = comm_codecs.make_codec(fed_cfg)
    stateful_attack = getattr(update_attack, "stateful", False)
    guard_on = getattr(fed_cfg, "update_guard", True)
    if faults is not None and not faults.active:
        faults_cfg = None                       # inactive == no injection
    else:
        faults_cfg = faults

    def round_fn(state: FedState, data):
        """data: client-stacked {x:(K,B,...), y:(K,B), eval_x, eval_y, n:(K,)}
        plus optional {avail:(K,)}."""
        rng, r_data, r_upd, r_sel, r_cli = jax.random.split(state.rng, 5)
        avail = data.get("avail", jnp.ones((K,), jnp.float32))
        t = state.round

        # ---- fault injection: stragglers miss the round deadline -------
        # a late client simply never arrives, so the straggle composes
        # with the whole availability path: selection, fitness masks, and
        # the stale_weight catch-up (a slot-team member that straggles
        # re-enters at stale weight, like any other unavailability)
        if faults_cfg is not None and faults_cfg.stragglers_active:
            avail = avail * faults_mod.sample_arrivals(
                faults_cfg, jax.random.fold_in(r_data, 11), K)

        if data_attack is not None:
            data = dict(data)
            data.update(data_attack(data, mal, r_data))

        # ---- local training (vmapped clients) --------------------------
        # partial-work fault: heterogeneous effective local epochs
        if faults_cfg is not None and faults_cfg.partial_active:
            eff_epochs = faults_mod.sample_epochs(
                faults_cfg, jax.random.fold_in(r_cli, 13), K,
                fed_cfg.local_epochs)
        else:
            eff_epochs = jnp.full((K,), fed_cfg.local_epochs, jnp.int32)
        keys = jax.random.split(r_cli, K)
        with obs_annotate("client_update"):
            locals_, (gl, ga, ll, la) = jax.vmap(
                client_update, in_axes=(None, 0, 0, 0))(state.params, data,
                                                        keys, eff_epochs)
            updates = jax.tree_util.tree_map(
                lambda w_k, w: w_k - w[None], locals_, state.params)

        att_carry = state.attacker
        if update_attack is not None:
            with obs_annotate("update_attack"):
                if stateful_attack:
                    # cross-round adaptive attacker: reads last round's
                    # gate outcome from the carry, re-tunes its blend,
                    # and hands back the adapted carry (completed after
                    # the gate below)
                    updates, att_carry = update_attack(
                        updates, mal, r_upd, state.attacker)
                else:
                    updates = update_attack(updates, mal, r_upd)

        # ---- client->server transport (repro/comm/) ---------------------
        # the codec runs CLIENT-side, after the attacker corrupted its own
        # update: only the encoded wire format crosses the boundary, and
        # only its measured bytes are billed.  EF residuals re-inject last
        # round's compression error before encoding.
        enc, new_ef = None, state.ef
        with obs_annotate("codec"):
            if codec is not None:
                enc, dec, new_ef = error_feedback.compress(
                    codec, updates, state.ef,
                    # fold_in, not split: the existing rng streams (and
                    # with them the compress="none" histories) stay
                    # untouched
                    rng=jax.random.fold_in(r_upd, 7) if codec.stochastic
                    else None)
                bytes_up_pc = comm_codecs.wire_bytes_per_client(enc)
                updates = dec
            else:
                bytes_up_pc = comm_codecs.dense_bytes_per_client(updates)
            bytes_down_pc = comm_codecs.param_bytes(state.params)

        # ---- fitness election (only when h(t): FFA/NAT rounds) -----------
        with obs_annotate("selection"):
            q = fitness.data_quality(data["n"], avail)
            th = jnp.where(t == 1, jnp.zeros((K,)),
                           fitness.theta(gl, ga, ll, la))

            alpha = jnp.where(
                jnp.array(fed_cfg.dynamic_alpha),
                fitness.dynamic_alpha(q, th, avail),
                jnp.float32(fed_cfg.alpha))
            scores = fitness.score(q, th, alpha)
            if fed_cfg.trust_in_fitness:
                # dynamic client scoring: the cosine-gate trust EWMA
                # scales the fitness score, so repeatedly-gated clients
                # stop being elected.  gate_trust is exactly 1.0 until
                # someone is gated, keeping the fold behavior-preserving
                # on clean runs.
                scores = scores * state.gate_trust

            if fed_cfg.algorithm == "fedfits":
                new_team = selection.fedfits_select(
                    scores, fed_cfg.beta, avail, r_sel,
                    floor_prob=fed_cfg.participation_floor,
                    explore_eps=fed_cfg.explore_eps)
                new_team = jnp.where(t == 1, avail, new_team)
                team = jnp.where(state.h, new_team, state.team * avail)
            elif fed_cfg.algorithm == "fedavg":
                team = selection.fedavg_select(avail)
            elif fed_cfg.algorithm == "fedrand":
                team = selection.fedrand_select(avail, fed_cfg.fedrand_c,
                                                r_sel)
            elif fed_cfg.algorithm == "fedpow":
                d = fed_cfg.fedpow_d or K
                m = fed_cfg.fedpow_m or max(K // 2, 1)
                team = selection.fedpow_select(gl, avail, d, m, r_sel,
                                               n=data["n"])
            else:
                raise ValueError(fed_cfg.algorithm)

        # ---- fault injection: mid-round dropout ------------------------
        # a SELECTED client computes its update (so it is still billed,
        # compute and uplink both — the loss is on the server side of the
        # wire) but the update never reaches the aggregate.  Dropped
        # clients are NOT stale catch-up contributors: stale covers
        # clients that never arrived, not updates lost in flight.
        if faults_cfg is not None and faults_cfg.dropout_active:
            lost = faults_mod.sample_dropout(
                faults_cfg, jax.random.fold_in(r_sel, 12), team)
        else:
            lost = jnp.zeros((K,), jnp.float32)
        delivered = team * (1.0 - lost)

        # ---- aggregation -------------------------------------------------
        # async catch-up (Table II gap 2): slot-team members that went
        # unavailable this round still contribute at stale_weight
        stale = fed_cfg.stale_weight * state.team * (1.0 - avail)
        part = jnp.clip(delivered + stale, 0.0, 1.0)

        # ---- aggregation-boundary guard --------------------------------
        # a crashed or hostile client delivering NaN/Inf or an
        # absurd-norm update is REJECTED here — zeroed, masked out of
        # every aggregation path (fused and reference), and penalised
        # via the gate-trust EWMA below — instead of poisoning the
        # global model.  On sane inputs the sanitise pass is a bitwise
        # identity, so clean histories are unchanged.  Billing uses the
        # PRE-rejection masks: the rejected client did the work and
        # crossed the wire (billed-but-lost, like mid-round dropout).
        part_pre, stale_pre = part, stale
        rejected = jnp.zeros((K,), jnp.float32)
        g_nonfinite = g_norm = jnp.float32(0.0)
        if guard_on:
            with obs_annotate("sanitize"):
                if state.tele is not None:
                    # guard rejections split by kind — shares the guard's
                    # own reductions (CSE), a pure readout
                    nf, nr = aggregation.rejection_kinds(
                        updates, (part > 0).astype(jnp.float32),
                        norm_mult=fed_cfg.guard_norm_mult)
                    g_nonfinite, g_norm = nf.sum(), nr.sum()
                updates, _, rejected = aggregation.sanitize_updates(
                    updates, (part > 0).astype(jnp.float32),
                    norm_mult=fed_cfg.guard_norm_mult)
            delivered = delivered * (1.0 - rejected)
            stale = stale * (1.0 - rejected)
            part = jnp.clip(delivered + stale, 0.0, 1.0)
        with obs_annotate("aggregate"):
            if fed_cfg.paper_exact_agg:
                # Algorithm 1's size-proportional FedAvg step.  The paper
                # writes n_k/|S_t|, but data["n"] carries REAL partition
                # sizes, so dividing raw counts by the team size would
                # scale the update by ~mean(n_k) (hundreds x); the convex
                # combination the algorithm means is
                # n_k / sum_{j in S_t} n_j
                w = data["n"].astype(jnp.float32) * delivered
                w = w / jnp.maximum(w.sum(), 1e-12)
                agg = jax.tree_util.tree_map(
                    lambda l: jnp.tensordot(w.astype(l.dtype), l,
                                            axes=(0, 0)),
                    updates)
            else:
                weights = data["n"].astype(jnp.float32) * state.trust \
                    * (delivered + stale)
                # an int8 uplink aggregates STRAIGHT from the wire codes
                # (dequant in VMEM inside the fused Eq.-11 passes,
                # bit-identical to aggregating the decoded `updates`)
                agg = aggregation.aggregate(
                    updates, weights, (part > 0).astype(jnp.float32),
                    fed_cfg, enc=enc)
        with obs_annotate("writeback"):
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), state.params, agg)

            # ---- slot & trust state --------------------------------------
            theta_team = fitness.team_theta(th, team)
            new_slot, h_next = slots.update(state.slot, theta_team, t,
                                            fed_cfg.msl, fed_cfg.pft)
            new_trust = aggregation.update_trust(state.trust, scores, team,
                                                 fed_cfg.trust_decay)

            # gate-trust EWMA (dynamic client scoring): participants whose
            # update points AWAY from the round's robust aggregate (cosine
            # below the gate threshold — the same rejection the Eq.-11
            # cosine gate applies in-kernel) see their trust decay toward
            # 0; clean participants decay toward 1, non-participants hold.
            cos = aggregation.cosine_to_ref(updates, agg)
            gated = ((cos < fed_cfg.cosine_outlier_thresh)
                     & (part > 0)).astype(jnp.float32)
            # guard rejections count as gate failures too: the EWMA runs
            # over PRE-rejection participants so a rejected delivery
            # decays trust exactly like a cosine-gated one (bad == gated
            # when no row was rejected, so clean histories are
            # bit-identical)
            bad = jnp.maximum(gated, rejected)
            new_gate_trust = jnp.where(
                part_pre > 0,
                fed_cfg.trust_decay * state.gate_trust
                + (1.0 - fed_cfg.trust_decay) * (1.0 - bad),
                state.gate_trust)
        if stateful_attack:
            # complete the adaptive attacker's carry: it reads THIS
            # round's gate outcome next round
            with obs_annotate("update_attack"):
                att_carry = update_attack.observe(att_carry, bad)

        # cost accounting: FFA rounds bill every available client, slot
        # rounds the present team — PLUS, in both, the stale catch-up
        # clients: they went unavailable but still trained and submitted
        # an update at stale_weight, so their client-round is real work.
        # The paper-exact branch weighs by n_k * team only (no stale
        # contribution enters the aggregate), so nothing extra is billed.
        # Bytes are MEASURED, not modelled: every billed client-round
        # moves one dense model down and one ENCODED update up (the
        # actual wire sizes — dtype itemsizes, codes, scales, indices)
        billed = jnp.where(state.h, avail.sum(), team.sum())
        if not fed_cfg.paper_exact_agg:
            billed = billed + (stale_pre > 0).sum()

        # ---- telemetry readout (repro/obs/) -----------------------------
        # pure readouts of values the round already produced; nothing
        # downstream reads them back, so on/off runs are bit-identical
        new_tele, obs_metrics = state.tele, {}
        if state.tele is not None:
            wts = data["n"].astype(jnp.float32) * state.trust
            vals = {
                "gate/cosine_rejected": gated.sum(),
                "guard/nonfinite": g_nonfinite,
                "guard/norm": g_norm,
                "select/team_size": team.sum(),
                "select/available": avail.sum(),
                "agg/fresh_mass": (wts * delivered).sum(),
                "agg/stale_mass": (wts * stale).sum(),
                "cohort/trust_q": obs_counters.quantiles(new_trust),
                "cohort/gate_trust_q": obs_counters.quantiles(
                    new_gate_trust),
                "cohort/fitness_q": obs_counters.quantiles(scores),
                "wire/bytes_up": billed * bytes_up_pc,
                "wire/bytes_down": billed * bytes_down_pc,
                "fault/lost": lost.sum(),
            }
            new_tele = obs_counters.accumulate(state.tele, vals, "sync")
            obs_metrics = obs_counters.metric_keys(vals)
        with obs_annotate("writeback"):
            new_clients = state.clients._replace(
                # fitness EWMA at compute time (the population-store
                # prior; the sync selection path keeps using the fresh
                # scores, so this column is bookkeeping, not a behavior
                # change)
                fitness=fed_cfg.trust_decay * state.clients.fitness
                + (1.0 - fed_cfg.trust_decay) * scores,
                trust=new_trust,
                gate_trust=new_gate_trust,
                staleness=jnp.where(part > 0, 0,
                                    state.clients.staleness + 1),
                failures=state.clients.failures + rejected,
                cum_selected=state.clients.cum_selected + team,
                ef=new_ef)
        new_state = FedState(
            params=new_params, team=team, alpha=alpha,
            slot=new_slot, h=h_next, rng=rng, round=t + 1,
            cost_client_rounds=state.cost_client_rounds + billed,
            cost_bytes_up=state.cost_bytes_up + billed * bytes_up_pc,
            cost_bytes_down=state.cost_bytes_down + billed * bytes_down_pc,
            clients=new_clients, attacker=att_carry, tele=new_tele)
        metrics = {
            "theta": th, "score": scores, "team": team, "alpha": alpha,
            "theta_team": theta_team, "h_next": h_next,
            "global_loss_mean": (gl * avail).sum() / jnp.maximum(avail.sum(), 1),
            "local_loss_mean": (ll * avail).sum() / jnp.maximum(avail.sum(), 1),
            "team_size": team.sum(),
            # robustness / fairness block (scenario engine, ROADMAP item 5)
            "gate_trust": new_gate_trust,
            "gated_frac": gated.sum() / jnp.maximum(part.sum(), 1.0),
            "guard_rejected": rejected.sum(),
            "fault_lost": lost.sum(),
            "fault_eff_epochs": eff_epochs.astype(jnp.float32).mean(),
            **fairness.round_fairness(ga, avail, state.cum_selected + team),
            **obs_metrics,
        }
        return new_state, metrics

    return round_fn


@jax.jit
def round_keys(rng, ts):
    """``jax.random.fold_in(rng, t)`` for every step ``t`` of a chunk, as
    a tuple of keys: one program for the chunk's keys in place of one
    ``fold_in`` per round, bit-identical to them."""
    return tuple(jax.vmap(jax.random.fold_in, (None, 0))(rng, ts))


def scoped_eval(eval_fn):
    """``eval_fn`` with its ops under the device scope ``server_eval``
    (None stays None)."""
    if eval_fn is None:
        return None

    def scoped(params):
        with obs_annotate("server_eval"):
            return eval_fn(params)

    return scoped


def run(model, fed_cfg, data_fn, n_rounds, rng, *, eval_fn=None,
        data_attack=None, update_attack=None, malicious=None,
        faults=None, driver="scan", chunk_rounds=8, telemetry=None):
    """Drives n_rounds of FL. data_fn(round, rng) -> client-stacked batch.
    eval_fn(params) -> dict of server-side metrics (optional, per round).
    Returns (final_state, history list of dicts).

    driver="scan" (default): rounds run through the shared chunked-scan
    driver (core/driver.py — donated carry, on-device metric history,
    double-buffered batch staging; the pod engine drives multi-round
    training through the same subsystem).  data_fn stays a host
    callable, handed the same ``fold_in(rng, t)`` keys, derived for a
    whole chunk in one program (``round_keys``); device-array batches
    are stacked inside the compiled chunk.  Availability sampling moves
    inside the scan body (same fold_in streams), so the history is
    bit-for-bit identical to driver="python", the original per-round jit
    loop kept for parity testing."""
    r_init, r_run = jax.random.split(rng)
    params = model.init(r_init)
    eval_fn = scoped_eval(eval_fn)
    att = update_attack if getattr(update_attack, "stateful", False) else None
    state = init_state(params, fed_cfg.n_clients, fed_cfg, r_run,
                       attacker=att)
    if telemetry is not None:
        telemetry.bind_engine("sync")
        if telemetry.counters:
            state = state._replace(
                tele=obs_counters.init_column("sync", fed_cfg))
    round_fn = make_round(model, fed_cfg, data_attack=data_attack,
                          update_attack=update_attack, malicious=malicious,
                          faults=faults)
    K = fed_cfg.n_clients

    if driver == "python":
        round_jit = jax.jit(round_fn)
        eval_jit = jax.jit(eval_fn) if eval_fn is not None else None
        rec = getattr(telemetry, "tracer", None)
        history = []
        for t in range(1, n_rounds + 1):
            batch = dict(data_fn(t, jax.random.fold_in(rng, t)))
            if fed_cfg.avail_prob < 1.0:
                # always feed avail (ones at t=1) so every round runs the
                # same compiled program as the scan body — bit-for-bit
                a = (jax.random.uniform(jax.random.fold_in(rng, 10_000 + t),
                                        (K,))
                     < fed_cfg.avail_prob).astype(jnp.float32)
                a = a.at[0].set(1.0)               # never an empty round
                batch["avail"] = a if t > 1 else jnp.ones((K,), jnp.float32)
            w0 = telemetry.now_us() if telemetry is not None else 0.0
            # device_get syncs every round under this driver, so the
            # span measures the whole round
            with span("round", rec, round=t):
                state, metrics = round_jit(state, batch)
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
        t, batch = xs
        if fed_cfg.avail_prob < 1.0:
            a = (jax.random.uniform(jax.random.fold_in(rng, 10_000 + t),
                                    (K,))
                 < fed_cfg.avail_prob).astype(jnp.float32)
            a = a.at[0].set(1.0)                   # never an empty round
            batch = dict(batch)
            batch["avail"] = jnp.where(t > 1, a, jnp.ones((K,), jnp.float32))
        st, metrics = round_fn(st, batch)
        if eval_fn is not None:
            metrics = {**metrics, **eval_fn(st.params)}
        return st, metrics

    def feed(ts):
        keys = round_keys(rng, np.asarray(ts, np.uint32))
        return [data_fn(t, k) for t, k in zip(ts, keys)]

    drv = scan_driver.ScanDriver(body, chunk_steps=chunk_rounds)
    return drv.run(state, feed, n_rounds, t0=1, index_key="round",
                   telemetry=telemetry)
