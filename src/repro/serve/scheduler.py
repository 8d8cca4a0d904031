"""Slot-based request scheduler for continuous batching (ROADMAP item 3).

Slot protocol
-------------
* A request occupies exactly one decode slot from admission to
  eviction; the decode batch is always ``(max_slots, 1)`` — no dynamic
  shapes, one compiled decode step reused forever.
* Admission picks the first free slot by the integer-key argsort idiom
  the async DeliveryBuffer uses (core/async_engine.py): free slots keep
  their index as the sort key, occupied slots sort after every free one.
* ALL pages a request can ever need — ``ceil(budget / page_size)`` with
  ``budget = min(plen + max_new - 1, max_len)`` KV rows — are allocated
  at admission.  That makes the scheduler exhaustion-free by
  construction (an admitted request can always finish), keeps every
  shape static, and still buys the paged wins: slots share one pool,
  eviction frees pages with a masked scatter (no compaction copy), and
  short requests return their pages the step they finish.
* Eviction happens inside the decode step: a slot whose new KV length
  reaches its budget (or that sampled ``eos_id``) flips inactive and its
  pages scatter back into the free mask — the next admission reuses
  them without any copy.

Token accounting: the first generated token is sampled from the prefill
logits at admission, so a request emits ``1 + (budget - plen)`` tokens
total = ``max_new`` (when not truncated by ``max_len``).  ``max_new = 1``
requests complete at admission and never occupy a slot.

:class:`HostLedger` is the host-side mirror of the device scheduler:
admission decisions (slot choice, page availability) are pure functions
of the admit/evict history, so the host can decide *whether* to admit
without a device sync, and the device ``ok`` flag only asserts
agreement.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving shapes + policy knobs (all jit-constants)."""
    max_slots: int = 8          # decode batch width
    page_size: int = 16         # KV rows per page
    max_len: int = 256          # per-request KV row cap (prompt + gen)
    prompt_pad: int = 32        # static prefill width (prompts padded)
    num_pages: int = 0          # pool size; 0 -> worst-case full budget
    eos_id: int = -1            # sampled token that evicts; -1 = never
    temperature: float = 0.0    # 0 = argmax decoding
    kv_int8: bool = False       # int8 page pools + per-row scales
    attn: str = "pallas"        # pallas (paged flash-decode) | ref (the
                                # dense gather oracle)

    @property
    def pages_per_slot(self) -> int:
        return -(-self.max_len // self.page_size)

    @property
    def total_pages(self) -> int:
        return self.num_pages or self.max_slots * self.pages_per_slot


@dataclasses.dataclass(frozen=True)
class Request:
    req_id: int
    tokens: Tuple[int, ...]     # prompt token ids (1 <= len <= prompt_pad)
    max_new: int                # tokens to generate (incl. the admit token)


class SlotState(NamedTuple):
    """Per-slot device state riding the donated decode carry."""
    tok: jnp.ndarray        # (S, 1) i32   last emitted token per slot
    length: jnp.ndarray     # (S,)   i32   valid KV rows per slot
    budget: jnp.ndarray     # (S,)   i32   KV length at which the slot ends
    active: jnp.ndarray     # (S,)   f32   1 = live request
    req_id: jnp.ndarray     # (S,)   i32   owning request (attribution)
    alloc: jnp.ndarray      # (S,)   i32   pages owned by the slot
    table: jnp.ndarray      # (S, maxp) i32  page table
    free: jnp.ndarray       # (N,)   f32   free-page mask over the pool
    tele: dict              # obs counter column (serve/* registry slice)
    key: jnp.ndarray        # PRNG carry (split every step)


def init_slot_state(scfg: ServeConfig, key, tele) -> SlotState:
    s, maxp, n = scfg.max_slots, scfg.pages_per_slot, scfg.total_pages
    return SlotState(
        tok=jnp.zeros((s, 1), jnp.int32),
        length=jnp.zeros((s,), jnp.int32),
        budget=jnp.zeros((s,), jnp.int32),
        active=jnp.zeros((s,), jnp.float32),
        req_id=jnp.full((s,), -1, jnp.int32),
        alloc=jnp.zeros((s,), jnp.int32),
        table=jnp.zeros((s, maxp), jnp.int32),
        free=jnp.ones((n,), jnp.float32),
        tele=tele, key=key)


def kv_budget(plen: int, max_new: int, scfg: ServeConfig) -> int:
    """KV rows a request can occupy (host-side mirror of the device
    arithmetic in the admit step)."""
    return min(plen + max_new - 1, scfg.max_len)


def pages_needed(plen: int, max_new: int, scfg: ServeConfig) -> int:
    return -(-kv_budget(plen, max_new, scfg) // scfg.page_size)


def pick_free_slot(active):
    """First inactive slot by integer-key argsort (DeliveryBuffer
    idiom); (slot, has_slot)."""
    s = active.shape[0]
    idx = jnp.arange(s)
    order = jnp.argsort(jnp.where(active > 0, s + idx, idx))
    return order[0], active.sum() < s


def take_pages(free, need, maxp):
    """Claim ``need`` pages from the free mask: returns a (maxp,) page
    row (unused tail = 0), the feasibility flag, and the updated mask.
    Nothing is taken when infeasible."""
    n = free.shape[0]
    idx = jnp.arange(n)
    order = jnp.argsort(jnp.where(free > 0, idx, n + idx))
    ok = need <= free.sum()
    j = jnp.arange(maxp)
    takes = (j < need) & ok
    pages = jnp.where(takes, order[jnp.clip(j, 0, n - 1)], 0)
    free2 = free.at[jnp.where(takes, pages, n)].set(0.0, mode="drop")
    return pages, ok, free2


def validate_request(r: Request, scfg: ServeConfig) -> None:
    plen = len(r.tokens)
    if not 1 <= plen <= scfg.prompt_pad:
        raise ValueError(f"req {r.req_id}: prompt length {plen} outside "
                         f"[1, prompt_pad={scfg.prompt_pad}]")
    if plen > scfg.max_len:
        raise ValueError(f"req {r.req_id}: prompt longer than max_len")
    if r.max_new < 1:
        raise ValueError(f"req {r.req_id}: max_new must be >= 1")
    if pages_needed(plen, r.max_new, scfg) > scfg.total_pages:
        raise ValueError(f"req {r.req_id}: needs more pages than the pool")


class HostLedger:
    """Host mirror of the device scheduler's admit/evict bookkeeping.

    The device admit step is deterministic given the admit/evict
    history (first free slot, first free pages), so the host replays
    the same arithmetic to decide *whether* the next request fits —
    no device sync on the admission path.  The engine asserts the
    device ``ok``/slot agree with the mirror on every admit.
    """

    def __init__(self, scfg: ServeConfig):
        self.scfg = scfg
        self.free_pages = scfg.total_pages
        self.slot_pages = [0] * scfg.max_slots
        self.active = [False] * scfg.max_slots

    @property
    def n_active(self) -> int:
        return sum(self.active)

    def can_admit(self, need: int) -> bool:
        return (not all(self.active)) and need <= self.free_pages

    def next_slot(self) -> int:
        return self.active.index(False)

    def admit_at(self, slot: int, need: int) -> None:
        assert not self.active[slot] and need <= self.free_pages
        self.active[slot] = True
        self.slot_pages[slot] = need
        self.free_pages -= need

    def evict(self, slot: int) -> None:
        assert self.active[slot]
        self.active[slot] = False
        self.free_pages += self.slot_pages[slot]
        self.slot_pages[slot] = 0
