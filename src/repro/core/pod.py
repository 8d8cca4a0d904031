"""PodEngine: one FedFiTS round as a single SPMD program for the big
architectures (DESIGN.md §2 "FL-on-pod").

Mapping:
  * the C client groups partition the global batch along the mesh "data"
    axis; per-client losses come from a (C, B/C, S) reshape of the
    per-token loss — no cross-client collectives in the local phase;
  * E local epochs = E-step gradient accumulation per client group
    (first-order-equivalent to local SGD at pod scale; see DESIGN.md);
  * slot-internal aggregation = the trust/team/size-weighted sum
    sum_c w_c * grad_c, realised as ONE weighted backward pass (psum over
    "data"); cross-slot aggregation = the same reduction's "pod" axis leg;
  * fitness (theta/score/threshold/team/trust/slot counters) are O(C)
    scalars carried in PodState — the entire round jits into one program.

``robust='per_client'`` materialises per-client grads (vmap) and runs the
coordinate-robust aggregators through the two-pass Pallas engine
(kernels/robust_pipeline.py): each (C, n_leaf) grad leaf is streamed
twice instead of sorted ~4 times, leaf-wise (segment-table grid — no
(C, N_params) flatten concatenate).  With ``agg_mesh`` the flattened
param axis additionally shards over the mesh
(``aggregation.aggregate(..., mesh=)``): every device streams its shard in
both passes and only the (C,) cosine partials (+ Krum's Gram matrix)
cross devices in one psum, so per-device HBM traffic drops by the mesh
size instead of replicating the whole grad matrix; the grads are
constrained to the ``client_flat_specs`` layout before the shard_map
boundary, so the vmap'd backward emits them in place — no reshard
collective at the boundary.  Memory-feasible for <=20B models (see
DESIGN.md §2) and used by the smoke tests.

Multi-round training runs through ``pod.run`` on the shared chunked-scan
driver (core/driver.py): donated carry, on-device metric history, and
sharding-aware double-buffered batch prefetch — the same subsystem that
drives ``fedfits.run`` (wired end-to-end by ``launch/train.py``).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import codecs as comm_codecs, error_feedback
from repro.core import aggregation, driver as scan_driver, fitness, \
    selection, slots
from repro.models import transformer
from repro.obs.trace import span
from repro.optim import optimizers


class PodFedState(NamedTuple):
    team: jnp.ndarray          # (C,)
    trust: jnp.ndarray         # (C,)
    alpha: jnp.ndarray
    slot: slots.SlotState
    h: jnp.ndarray
    rng: jnp.ndarray
    round: jnp.ndarray
    cum_selected: jnp.ndarray
    ef: Any = None             # per-client-group EF residual (compress on)


class PodState(NamedTuple):
    params: Any
    opt_state: Any
    fed: PodFedState
    step: jnp.ndarray


def init_pod_state(params, opt_init, C, fed_cfg, rng):
    ef = None
    if getattr(fed_cfg, "compress", "none") != "none" \
            and fed_cfg.error_feedback:
        # (C, ...) residual matching the per-client grad tree of the
        # robust='per_client' path — rides the ScanDriver donated carry
        ef = jax.tree_util.tree_map(
            lambda p: jnp.zeros((C,) + p.shape, p.dtype), params)
    return PodState(
        params=params,
        opt_state=opt_init(params),
        fed=PodFedState(
            team=jnp.ones((C,), jnp.float32),
            trust=jnp.full((C,), 0.5, jnp.float32),
            alpha=jnp.float32(fed_cfg.alpha),
            slot=slots.init_slot_state(),
            h=jnp.array(True),
            rng=rng,
            round=jnp.int32(1),
            cum_selected=jnp.zeros((C,), jnp.float32),
            ef=ef,
        ),
        step=jnp.int32(0),
    )


def per_client_metrics(params, cfg, batch, C):
    """Per-client (loss, acc) from one forward. batch tokens: (GB, S)."""
    hidden, _, aux = transformer.forward(
        params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        image_embeds=batch.get("image_embeds"), collect_logits=False)
    GB, S, _ = hidden.shape
    targets = batch["targets"]
    chunk = cfg.loss_chunk or S
    chunk = min(chunk, S)
    n = S // chunk

    def body(carry, xs):
        hc, tc = xs                                  # (GB, chunk, d), (GB, chunk)
        logits = transformer.lm_head(params, cfg, hc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
        correct = (jnp.argmax(logits, -1) == tc).astype(jnp.float32)
        ls, cs = carry
        return (ls + (logz - gold).sum(1), cs + correct.sum(1)), None

    h = hidden[:, : n * chunk].reshape(GB, n, chunk, -1).transpose(1, 0, 2, 3)
    t = targets[:, : n * chunk].reshape(GB, n, chunk).transpose(1, 0, 2)
    (loss_tok, acc_tok), _ = jax.lax.scan(
        body, (jnp.zeros((GB,), jnp.float32), jnp.zeros((GB,), jnp.float32)),
        (h, t), unroll=n if cfg.scan_unroll else 1)
    denom = float(n * chunk)
    loss_c = loss_tok.reshape(C, GB // C).mean(1) / denom
    acc_c = acc_tok.reshape(C, GB // C).mean(1) / denom
    return loss_c, acc_c, aux


def make_train_step(model_cfg, fed_cfg, train_cfg, *, robust=None,
                    eval_frac=4, zero1_shardings=None, agg_mesh=None,
                    agg_axes=None):
    """Returns train_step(state, batch) -> (state, metrics).

    batch: {tokens (GB, S), targets (GB, S), [embeds/image_embeds]}.
    GB % C == 0; client c owns rows [c*GB/C, (c+1)*GB/C).

    zero1_shardings: optional (compute_sh, master_sh) NamedSharding trees.
    When given, the step runs ZeRO-1: forward/backward on bf16 TP-sharded
    data-replicated weights (one all-gather per step over "data"), grads
    reduce-scattered back to the fully-sharded fp32 master + optimizer
    state. Baseline (None) keeps fp32 FSDPxTP weights in the matmuls and
    lets GSPMD pick the collectives.

    agg_mesh / agg_axes: with robust='per_client', shard the robust
    aggregation's flattened param axis over these mesh axes (default:
    every axis but "pod") via ``aggregation.aggregate(..., mesh=)`` —
    both fused passes then stream shard-locally instead of replicating
    the whole (C, N_params) grad matrix on every device.
    """
    C = fed_cfg.n_clients
    opt_init, opt_update = optimizers.make_optimizer(train_cfg)
    codec = comm_codecs.make_codec(fed_cfg)
    if codec is not None and robust != "per_client":
        raise ValueError(
            "FedConfig.compress needs robust='per_client': the weighted-"
            "backward path fuses aggregation into the backward pass, so "
            "no per-client update ever crosses a client->server boundary")

    def weighted_loss(params, batch, weights):
        loss_c, acc_c, aux = per_client_metrics(params, model_cfg, batch, C)
        total = jnp.sum(weights * loss_c) + aux
        return total, (loss_c, acc_c)

    def eval_slice(batch):
        """Held-out-ish slice: last 1/eval_frac of each client's rows."""
        def cut(x):
            if x is None or x.ndim < 2:
                return x
            GB = x.shape[0]
            bc = GB // C
            e = max(1, bc // eval_frac)
            xc = x.reshape(C, bc, *x.shape[1:])[:, -e:]
            return xc.reshape(C * e, *x.shape[1:])

        return {k: cut(v) for k, v in batch.items() if v is not None}

    def train_step(state: PodState, batch):
        fed = state.fed
        rng, r_sel = jax.random.split(fed.rng)
        t = fed.round
        new_ef = fed.ef
        bytes_up_pc = None

        # ---- round weights: team * trust * equal-size q (selection-aware) --
        w = fed.team * fed.trust
        w = w / jnp.maximum(w.sum(), 1e-12)

        if zero1_shardings is not None:
            # ZeRO-1: bf16 compute copy, replicated over "data"
            compute_sh, master_sh = zero1_shardings
            cparams = jax.lax.with_sharding_constraint(
                jax.tree_util.tree_map(
                    lambda p: p.astype(jnp.bfloat16), state.params),
                compute_sh)

            (_, (loss_c, acc_c)), grads = jax.value_and_grad(
                weighted_loss, has_aux=True)(cparams, batch, w)
            # reduce-scatter grads back onto the master layout
            grads = jax.lax.with_sharding_constraint(
                jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                       grads), master_sh)
        elif robust == "per_client":
            def client_grad(c):
                GB = batch["tokens"].shape[0] if batch.get("tokens") is not None \
                    else batch["embeds"].shape[0]
                bc = GB // C

                def one_loss(p):
                    sub = {k: (jax.lax.dynamic_slice_in_dim(v, c * bc, bc)
                               if (v is not None and v.ndim >= 1
                                   and v.shape[0] == GB) else v)
                           for k, v in batch.items()}
                    l, m = transformer.loss_fn(p, model_cfg, sub)
                    return l, m

                (l, m), g = jax.value_and_grad(one_loss, has_aux=True)(
                    state.params)
                return g, l, m["acc"]

            grads_c, loss_c, acc_c = jax.vmap(client_grad)(jnp.arange(C))
            enc = None
            if codec is not None:
                # client->server boundary: EF inject -> encode; only the
                # wire format reaches the server-side aggregation below
                enc, dec, new_ef = error_feedback.compress(
                    codec, grads_c, fed.ef,
                    rng=jax.random.fold_in(rng, 7) if codec.stochastic
                    else None)
                bytes_up_pc = comm_codecs.wire_bytes_per_client(enc)
                grads_c = dec
            grads = aggregation.aggregate(grads_c, w, fed.team, fed_cfg,
                                          enc=enc, mesh=agg_mesh,
                                          axes=agg_axes)
        else:
            (_, (loss_c, acc_c)), grads = jax.value_and_grad(
                weighted_loss, has_aux=True)(state.params, batch, w)

        if train_cfg.grad_clip:
            grads, gnorm = optimizers.clip_by_global_norm(
                grads, train_cfg.grad_clip)
        else:
            gnorm = optimizers.global_norm(grads)

        updates, new_opt = opt_update(grads, state.opt_state, state.params)
        new_params = optimizers.apply_updates(state.params, updates)

        # ---- fitness: GL/GA pre-update (have it), LL/LA post-update ------
        ev = eval_slice(batch)
        if zero1_shardings is not None:
            eval_params = jax.lax.with_sharding_constraint(
                jax.tree_util.tree_map(
                    lambda p: p.astype(jnp.bfloat16), new_params),
                zero1_shardings[0])
        else:
            eval_params = new_params
        ll_c, la_c, _ = per_client_metrics(eval_params, model_cfg, ev, C)
        # LM "accuracy" for Eq.(1): bounded (0,1] proxy exp(-loss) blended
        # with token accuracy (DESIGN.md §2 table)
        ga = 0.5 * (jnp.exp(-loss_c) + acc_c)
        la = 0.5 * (jnp.exp(-ll_c) + la_c)
        th = jnp.where(t == 1, jnp.zeros((C,)),
                       fitness.theta(loss_c, ga, ll_c, la))
        q = jnp.full((C,), 1.0 / C)                 # equal data shards on pod
        alpha = jnp.where(jnp.array(fed_cfg.dynamic_alpha),
                          fitness.dynamic_alpha(q, th),
                          jnp.float32(fed_cfg.alpha))
        scores = fitness.score(q, th, alpha)

        avail = jnp.ones((C,), jnp.float32)
        new_team = selection.fedfits_select(
            scores, fed_cfg.beta, avail, r_sel,
            floor_prob=fed_cfg.participation_floor,
            explore_eps=fed_cfg.explore_eps)
        new_team = jnp.where(t == 1, avail, new_team)
        team = jnp.where(fed.h, new_team, fed.team)

        theta_team = fitness.team_theta(th, team)
        new_slot, h_next = slots.update(fed.slot, theta_team, t,
                                        fed_cfg.msl, fed_cfg.pft,
                                        adaptive=True)
        new_trust = aggregation.update_trust(fed.trust, scores, team,
                                             fed_cfg.trust_decay)

        new_state = PodState(
            params=new_params, opt_state=new_opt,
            fed=PodFedState(team=team, trust=new_trust, alpha=alpha,
                            slot=new_slot, h=h_next, rng=rng, round=t + 1,
                            cum_selected=fed.cum_selected + team,
                            ef=new_ef),
            step=state.step + 1)
        metrics = {
            "loss": jnp.sum(w * loss_c), "acc": jnp.sum(w * acc_c),
            "grad_norm": gnorm, "theta_team": theta_team,
            "team_size": team.sum(), "alpha": alpha,
        }
        if bytes_up_pc is not None:
            # measured uplink bytes this round (encoded wire sizes)
            metrics["comm_bytes_up"] = jnp.float32(bytes_up_pc * C)
        return new_state, metrics

    return train_step


def run(state, train_step, batch_fn, n_rounds, *, driver="scan",
        chunk_rounds=8, batch_sharding=None, t0=0, on_chunk=None,
        telemetry=None):
    """Multi-round PodEngine training through the shared chunked-scan
    driver (core/driver.py) — the same subsystem that drives
    ``fedfits.run``.

    ``train_step`` is an (unjitted) step from ``make_train_step``;
    ``batch_fn(step)`` a host callable returning one batch dict.

    driver="scan" (default): ``chunk_rounds`` steps per ``lax.scan``
    chunk with the metric history on device (ONE device_get per chunk),
    the carry DONATED (params/opt-state update in place), and chunk k+1's
    batches double-buffer-staged while chunk k computes.  With
    ``batch_sharding`` (a NamedSharding tree matching one batch — e.g.
    ``launch.inputs.batch_shardings``) the staging ``device_put``s each
    chunk's batches directly onto their pod shards (sharding-aware
    prefetch) instead of the default device.

    driver="python": the original per-round jitted loop, kept for parity
    testing — the scan history is bit-for-bit equal to it.

    PRNG footgun: the donated carry aliases the arrays ``state`` was
    built from, including the key stored in ``PodFedState.rng`` — the
    first chunk deletes those buffers, so ``batch_fn`` must sample from a
    COPY of the key taken before this call (see launch/train.py).

    Returns (final_state, history rows keyed by "step").
    ``on_chunk(state, rows)`` fires after each chunk (logging /
    checkpoint hook); the python driver fires it per round.

    ``telemetry`` (repro.obs.Telemetry) observes the drained rows and
    driver-level trace spans; the pod step publishes its existing
    metrics, so no extra carry column is attached here."""
    if telemetry is not None:
        telemetry.bind_engine("sync")

    def body(st, xs):
        _, batch = xs
        return train_step(st, batch)

    if driver == "python":
        step_jit = jax.jit(train_step, donate_argnums=(0,))
        put_sharding = batch_sharding
        rec = getattr(telemetry, "tracer", None)
        history = []
        for t in range(t0, t0 + n_rounds):
            batch = dict(batch_fn(t))
            if put_sharding is not None:
                batch = jax.device_put(batch, put_sharding)
            w0 = telemetry.now_us() if telemetry is not None else 0.0
            with span("round", rec, round=t):
                state, metrics = step_jit(state, batch)
                row = {k: jax.device_get(v) for k, v in metrics.items()}
            row["step"] = t
            if telemetry is not None:
                telemetry.observe_rows([row], w0, telemetry.now_us() - w0)
            if on_chunk is not None:
                on_chunk(state, [row])
            history.append(row)
        return state, history
    if driver != "scan":
        raise ValueError(driver)

    return scan_driver.run_chunked(
        body, state, batch_fn, n_rounds, chunk_steps=chunk_rounds, t0=t0,
        batch_sharding=batch_sharding, index_key="step", on_chunk=on_chunk,
        telemetry=telemetry)
