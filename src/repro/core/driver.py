"""Shared chunked ``lax.scan`` round driver for both FL engines.

Extracted from ``fedfits.run`` (PR 2's zero-copy scan loop) so the
simulation engine (core/fedfits.py) and the pod engine (core/pod.py)
drive multi-round training through ONE subsystem:

  * rounds run in ``chunk_steps``-sized ``jax.lax.scan`` chunks with the
    per-round metric history kept on device — ONE ``device_get`` per
    chunk instead of 2+ host syncs per round;
  * the chunk step DONATES its carry (``donate_argnums``) so
    params/opt-state update in place instead of allocating a fresh copy
    per chunk (batch buffers are pure inputs with nothing to alias, so
    they are not donated);
  * chunk batches are double-buffered: while chunk k computes, chunk
    k+1's batches are built on host and staged with an async
    ``jax.device_put`` so the host->device transfer overlaps compute;
  * **sharding-aware prefetch**: ``batch_sharding`` (a ``NamedSharding``
    tree matching ONE batch) makes ``stage_chunk`` put chunk k+1's
    stacked batches DIRECTLY onto their pod shards — the stacked
    (chunk, ...) buffers get the same sharding with a leading replicated
    chunk dim (``chunk_sharding``), so a sharded pod step reads its
    batch shard-locally instead of re-slicing a default-device copy
    (ROADMAP open item 3).

None of this changes numerics: a driver's history is bit-for-bit equal
to the per-step jitted python loop over the same body (parity-tested
for both engines).

PRNG aliasing footgun: the donated carry aliases whatever arrays the
caller built it from (e.g. the PRNG key stored in ``PodFedState.rng``).
The first chunk deletes those buffers, so any host-side sampler must
consume its key from a COPY taken before the first ``run`` call —
see ``launch/train.py`` and tests/test_driver.py.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs.trace import span


def chunk_sharding(batch_sharding):
    """Lift a per-batch ``NamedSharding`` tree to the stacked
    (chunk, ...) layout: same mesh/spec with a leading replicated chunk
    dim.  The scan streams the chunk axis, so only the per-step slice's
    sharding matters — and it matches the per-batch sharding exactly."""
    def lift(s):
        if isinstance(s, NamedSharding):
            return NamedSharding(s.mesh, P(None, *s.spec))
        return s

    return jax.tree_util.tree_map(
        lift, batch_sharding,
        is_leaf=lambda x: isinstance(x, NamedSharding))


def _stack(*xs):
    """Stack one leaf across the chunk's batches.  Host-built (numpy)
    batches stack on HOST so the subsequent sharded ``device_put`` is a
    single host->shard transfer; device-resident batches stack with
    ``jnp`` (pulling them back to host would cost a device->host copy)
    and pay one device->shards redistribution hop instead."""
    if any(isinstance(x, jax.Array) for x in xs):
        return jnp.stack(xs)
    return np.stack(xs)


def stage_chunk(batch_fn, ts, batch_sharding=None):
    """Build the stacked batches for steps ``ts`` and start their
    host->device transfer (async ``jax.device_put``) — called while the
    PREVIOUS chunk is still computing, so the upload overlaps compute.
    With ``batch_sharding`` (the STACKED sharding from
    ``chunk_sharding``) every batch buffer lands on its pod shards —
    directly from host memory for host-built batches, via one
    redistribution hop for already-device-resident ones; otherwise it
    stages onto the default device."""
    batches = [dict(batch_fn(t)) for t in ts]
    stacked = jax.tree_util.tree_map(_stack, *batches)
    if batch_sharding is not None:
        stacked = jax.device_put(stacked, batch_sharding)
    else:
        stacked = jax.device_put(stacked)
    return jnp.asarray(ts, jnp.int32), stacked


class ScanDriver:
    """Reusable chunked-scan driver around ``body(state, (t, batch)) ->
    (state, metrics)``.  The jitted chunk scan is built once, so repeated
    ``run`` calls (benchmarks, restarts) hit the jit cache."""

    def __init__(self, body: Callable, *, chunk_steps: int = 8,
                 batch_sharding=None, donate: bool = True):
        self.chunk_steps = int(chunk_steps)
        self._put_sharding = (chunk_sharding(batch_sharding)
                              if batch_sharding is not None else None)
        donate_argnums = (0,) if donate else ()

        def scan_chunk(st, ts, batches):
            return jax.lax.scan(body, st, (ts, batches))

        self._scan = jax.jit(scan_chunk, donate_argnums=donate_argnums)

    def stage(self, batch_fn, ts):
        return stage_chunk(batch_fn, ts, self._put_sharding)

    def run(self, state, batch_fn, n_steps, *, t0: int = 0,
            index_key: str = "step",
            on_chunk: Optional[Callable[[Any, list], None]] = None,
            telemetry=None):
        """Drive ``n_steps`` steps starting at ``t0``.  ``batch_fn(t)``
        is a host callable returning one batch dict.  Returns
        ``(final_state, history)`` — one row dict per step, each carrying
        its step index under ``index_key``.  ``on_chunk(state, rows)``
        fires after every chunk (logging / checkpoint hook).

        Each host phase of a chunk is one ``obs.trace.span``, with the
        chunk's first step as arg ``first``: ``driver.stage`` (the next
        chunk's ``batch_fn`` calls, stacking and ``device_put``),
        ``driver.dispatch`` (the scan call), ``driver.drain`` (the one
        ``device_get`` per chunk and the rows) and ``driver.hooks``
        (``telemetry.observe_rows`` and ``on_chunk``).  ``telemetry``
        (an ``repro.obs.Telemetry``) gets the rows with the chunk's
        host window, dispatch through drain; its ``tracer``, when set,
        records the spans too."""
        end = t0 + n_steps
        rec = getattr(telemetry, "tracer", None)

        def steps_of(s0):
            return list(range(s0, min(s0 + self.chunk_steps, end)))

        def stage(s0):
            with span("driver.stage", rec, first=s0):
                ts = steps_of(s0)
                return (ts, *self.stage(batch_fn, ts))

        history = []
        pending = stage(t0) if n_steps >= 1 else None
        next_t0 = t0 + self.chunk_steps
        while pending is not None:
            ts, ts_dev, stacked = pending
            first = ts[0]
            w0 = telemetry.now_us() if telemetry is not None else 0.0
            # dispatch is async: the scan runs while the next chunk stages
            with span("driver.dispatch", rec, first=first):
                state, mets = self._scan(state, ts_dev, stacked)
            pending = stage(next_t0) if next_t0 < end else None
            next_t0 += self.chunk_steps
            with span("driver.drain", rec, first=first):
                mets = jax.device_get(mets)            # one sync per chunk
                rows = []
                for j, t in enumerate(ts):
                    row = {k: v[j] for k, v in mets.items()}
                    row[index_key] = t
                    rows.append(row)
            with span("driver.hooks", rec, first=first):
                if telemetry is not None:
                    telemetry.observe_rows(rows, w0,
                                           telemetry.now_us() - w0)
                if on_chunk is not None:
                    on_chunk(state, rows)
            history.extend(rows)
        return state, history


def run_chunked(body, state, batch_fn, n_steps, *, chunk_steps=8, t0=0,
                batch_sharding=None, index_key="step", on_chunk=None,
                donate=True, telemetry=None):
    """One-shot convenience wrapper: build a ``ScanDriver`` and run it."""
    drv = ScanDriver(body, chunk_steps=chunk_steps,
                     batch_sharding=batch_sharding, donate=donate)
    return drv.run(state, batch_fn, n_steps, t0=t0, index_key=index_key,
                   on_chunk=on_chunk, telemetry=telemetry)
