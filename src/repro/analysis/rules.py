"""The invariant rule registry.

Each rule is a function over a :class:`RuleContext` (one traced entry
point: its jaxpr, optionally its compiled HLO text, and the entry's
declared expectations) that appends :class:`~repro.analysis.report.Finding`s.
Register with ``@register_rule(name, kind=...)``; ``kind='jaxpr'`` rules
always run, ``kind='hlo'`` rules run only when the entry was compiled
(some need a multi-device mesh and are skipped otherwise).

Rules shipped here:

``copy_lint``        no leaf-sized concatenate (flatten materialization)
                     on the aggregation path; in ``engine`` mode also no
                     leaf-sized transpose-fed reshape (a copy in disguise),
                     while the async buffer's axis-0 row concatenation
                     stays legal.
``rng_discipline``   every sampled key derives from a distinct
                     fold_in/split; no key class is consumed twice
                     (the scan==python bit-parity story depends on this).
``donation_audit``   a donated carry must actually alias in the compiled
                     executable's input_output_alias map, and a PRNG-key
                     carry leaf must come back advanced (not the same var).
``dtype_discipline`` accumulation stays fp32: no leaf-sized reduce/add/
                     contraction producing half precision, and at most one
                     leaf-sized fp32->half cast per half-precision output.
``pallas_budget``    per-pallas_call VMEM working-set estimate from the
                     grid_mapping's BlockSpecs; over-budget is an error,
                     lane-minor (minor dim < 128) block layouts are
                     reported as notes feeding the "(C,) lane-minor"
                     follow-up.
``fusion_count``     HBM-pass budget over the compiled ENTRY computation:
                     total bytes its kernels materialize, in multiples of
                     the cohort update payload, stays under the entry's
                     cap (a flatten/copy/re-sort chain doubles traffic).
``collective_lint``  per-entry byte allowlists over the compiled module's
                     collectives (e.g. aggregate_sharded may psum small
                     partials but never all-to-all).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.analysis import hlo as hlo_mod
from repro.analysis import traversal as tv
from repro.analysis.report import (SEV_ERROR, SEV_NOTE, EntryResult, Finding)

# VMEM is ~16 MiB/core; leave headroom for pipelining/semaphores.
VMEM_BYTES = 16 * 1024 * 1024
VMEM_BUDGET = int(0.75 * VMEM_BYTES)
LANE = 128


@dataclasses.dataclass
class Rule:
    name: str
    kind: str                       # "jaxpr" | "hlo"
    fn: Callable


RULES: Dict[str, Rule] = {}


def register_rule(name: str, kind: str = "jaxpr"):
    """Register an invariant rule. The decorated fn takes a RuleContext
    and appends findings/notes to it."""
    def deco(fn):
        RULES[name] = Rule(name, kind, fn)
        return fn
    return deco


@dataclasses.dataclass
class RuleContext:
    """One entry point under analysis, as seen by the rules."""
    entry_name: str
    jaxpr: object                           # ClosedJaxpr of the traced fn
    result: EntryResult
    hlo_text: Optional[str] = None          # compiled module text, if any
    # entry expectations (set by the entry-point registry):
    copy_mode: str = "off"                  # "strict" | "engine" | "off"
    copy_threshold: int = 0                 # eqn output size that counts
    collective_allowlist: Optional[Dict[str, int]] = None
    donate_must_alias: tuple = ()           # flat param numbers that must
                                            # alias (with their path labels)
    check_rng_advance: bool = False
    rules_off: tuple = ()                   # rule names disabled per entry
    hbm_pass_cap: Optional[float] = None    # max HBM-pass multiple of the
                                            # payload (fusion_count)
    hbm_payload_bytes: int = 0              # one pass worth of bytes
    hbm_bytes_threshold: int = 0            # min buffer size that counts

    def finding(self, rule, message, eqn=None, severity=SEV_ERROR):
        f = Finding(
            rule=rule, entry=self.entry_name, message=message,
            severity=severity,
            provenance=tv.eqn_provenance(eqn) if eqn is not None else "?",
            primitive=eqn.primitive.name if eqn is not None else None,
            shape=(str(eqn.outvars[0].aval)
                   if eqn is not None and eqn.outvars else None))
        self.result.findings.append(f)

    def note(self, message):
        self.result.notes.append(message)


def _out_size(eqn) -> int:
    aval = eqn.outvars[0].aval
    return int(np.prod(getattr(aval, "shape", ()) or (1,)))


# --------------------------------------------------------------------- #
# 1. copy lint                                                          #
# --------------------------------------------------------------------- #

@register_rule("copy_lint")
def copy_lint(ctx: RuleContext) -> None:
    """No leaf-sized flatten materialization on the aggregation path.

    strict (kernels): ANY concatenate with output >= threshold fires —
    the leaf-streaming engines must never rebuild a (C, N) flat matrix.
    engine (round engines): only minor-axis concatenates fire (a flatten
    glues leaves along the last axis); the async delivery buffer's
    leading-axis row concatenation of (rows, ...) stacks is legitimate.
    Both modes flag leaf-sized reshapes fed by a transpose — XLA must
    materialise the permuted operand to relayout it.
    """
    if ctx.copy_mode == "off":
        return
    producers = {}
    for j, eqn in tv.all_eqns(ctx.jaxpr):
        for ov in eqn.outvars:
            producers[id(ov)] = eqn
    for j, eqn in tv.all_eqns(ctx.jaxpr):
        name = eqn.primitive.name
        if name == "concatenate" and _out_size(eqn) >= ctx.copy_threshold:
            ndim = len(eqn.outvars[0].aval.shape)
            dim = eqn.params.get("dimension", 0)
            if ctx.copy_mode == "strict" or dim == ndim - 1:
                ctx.finding(
                    "copy_lint",
                    f"leaf-sized concatenate (axis {dim} of {ndim}d, "
                    f"{_out_size(eqn)} elems >= {ctx.copy_threshold}): "
                    "flatten materialization on the aggregation path",
                    eqn)
        elif name == "reshape" and _out_size(eqn) >= ctx.copy_threshold:
            src = producers.get(id(eqn.invars[0]))
            if src is not None and src.primitive.name == "transpose":
                ctx.finding(
                    "copy_lint",
                    f"leaf-sized reshape of a transposed operand "
                    f"({_out_size(eqn)} elems): forces a relayout copy",
                    eqn)


# --------------------------------------------------------------------- #
# 2. RNG discipline                                                     #
# --------------------------------------------------------------------- #

# ops that alias a key value (same bits, new var)
_KEY_ALIAS = {"random_wrap", "random_unwrap"}
# ops that derive fresh, independent key material (not a consumption)
_KEY_DERIVE = {"random_split", "random_fold_in", "random_seed",
               "random_clone"}
# ops that spend a key's entropy: sampling from the same class twice
# yields correlated streams
_KEY_CONSUME = {"random_bits"}
_CALL_LIKE = {"jit", "pjit", "closed_call", "core_call", "remat", "checkpoint",
              "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
              "scan", "while", "cond", "shard_map"}


def _is_key_var(v) -> bool:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "dtype"):
        return False
    try:
        import jax
        return jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key)
    except Exception:
        return False


def _inner_jaxpr_invars(eqn):
    """Map each sub-jaxpr of a call-like eqn to the slice of eqn.invars
    feeding its invars positionally (best effort across primitives)."""
    out = []
    name = eqn.primitive.name
    subs = list(tv.sub_jaxprs(eqn))
    if name == "cond":
        # invars[0] is the predicate/index; branches share invars[1:]
        for sub in subs:
            out.append((sub, list(eqn.invars[1:])))
        return out
    if name == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        cond_j, body_j = subs[0], subs[1]
        carry = list(eqn.invars[cn + bn:])
        out.append((cond_j, list(eqn.invars[:cn]) + carry))
        out.append((body_j, list(eqn.invars[cn:cn + bn]) + carry))
        return out
    # scan, pjit, shard_map, remat, custom_*: invars align positionally
    # (scan: consts + carry + xs == body invars, xs lose the lead axis)
    for sub in subs:
        out.append((sub, list(eqn.invars)))
    return out


class _UnionFind:
    def __init__(self):
        self.parent = {}

    def find(self, x):
        self.parent.setdefault(x, x)
        while self.parent[x] != x:
            self.parent[x] = self.parent[self.parent[x]]
            x = self.parent[x]
        return x

    def union(self, a, b):
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.parent[ra] = rb


def _consumption_events(jaxpr, memo) -> Dict[int, List[object]]:
    """Per-invar-position consumption events of one (open) jaxpr:
    position -> list of consuming eqns, counting nested call-like eqns
    by their inner jaxprs' consumption of the matching position.
    Also records intra-jaxpr reuse findings into memo['_reuse']."""
    key = id(jaxpr)
    if key in memo:
        return memo[key]
    memo[key] = {}                          # cycle guard

    uf = _UnionFind()
    for j, eqn in [(jaxpr, e) for e in jaxpr.eqns]:
        if eqn.primitive.name in _KEY_ALIAS:
            uf.union(id(eqn.invars[0]), id(eqn.outvars[0]))

    # class -> list of consumer eqns (one entry per consumption event)
    events: Dict[int, List[object]] = {}

    def consume(var, eqn, times=1):
        root = uf.find(id(var))
        events.setdefault(root, []).extend([eqn] * times)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _KEY_CONSUME:
            consume(eqn.invars[0], eqn)
        elif name in _CALL_LIKE:
            for sub, outer_vars in _inner_jaxpr_invars(eqn):
                inner = _consumption_events(sub, memo)
                for pos, consumers in inner.items():
                    if pos < len(outer_vars) and consumers:
                        v = outer_vars[pos]
                        if hasattr(v, "aval"):   # skip Literals
                            consume(v, eqn, times=len(consumers))
        # _KEY_DERIVE and everything else: no consumption; their outputs
        # are fresh classes (slice/squeeze of split outputs likewise)

    reuse = memo.setdefault("_reuse", [])
    invar_roots = {uf.find(id(v)): i for i, v in enumerate(jaxpr.invars)}
    per_pos: Dict[int, List[object]] = {}
    for root, consumers in events.items():
        if len(consumers) >= 2:
            reuse.append((jaxpr, consumers))
        if root in invar_roots:
            per_pos[invar_roots[root]] = consumers
    memo[key] = per_pos
    return per_pos


@register_rule("rng_discipline")
def rng_discipline(ctx: RuleContext) -> None:
    """No PRNG key class may be consumed twice. Keys consumed by sampling
    (``random_bits``) or handed to a call-like eqn whose body samples them
    count as spent; ``split``/``fold_in`` derive fresh classes and do not.
    Two sampling eqns fed from one wrap/unwrap alias class means two
    correlated streams — exactly the bug class that breaks the
    scan==python bit-parity contract."""
    if "rng_discipline" in ctx.rules_off:
        return
    memo: dict = {}
    _consumption_events(ctx.jaxpr.jaxpr, memo)
    seen = set()
    for jaxpr, consumers in memo.get("_reuse", []):
        sig = (id(jaxpr), tuple(sorted(id(e) for e in set(consumers))))
        if sig in seen:
            continue
        seen.add(sig)
        prims = sorted({e.primitive.name for e in consumers})
        ctx.finding(
            "rng_discipline",
            f"PRNG key consumed {len(consumers)}x by {prims}: every "
            "sampling site must use a distinct fold_in/split derivation",
            consumers[-1])


# --------------------------------------------------------------------- #
# 3. donation audit                                                     #
# --------------------------------------------------------------------- #

@register_rule("donation_audit", kind="hlo")
def donation_audit(ctx: RuleContext) -> None:
    """Donated carries must actually alias. ``donate_argnums`` is a
    request — XLA silently drops it (and copies) when shapes/dtypes drift
    between carry-in and carry-out or the input stays live past the
    output write, so the executable's ``input_output_alias`` map is the
    only ground truth.  The entry declares WHICH flat params must alias
    (the heavy carry buffers: params, opt state, EF residuals, delivery
    buffer rows, the rng) — tiny bookkeeping scalars XLA chooses to copy
    are not the contract."""
    if not ctx.donate_must_alias or ctx.hlo_text is None:
        return
    aliased = hlo_mod.aliased_param_numbers(ctx.hlo_text)
    missing = [(i, label) for i, label in ctx.donate_must_alias
               if i not in aliased]
    if missing:
        ctx.finding(
            "donation_audit",
            f"donated carry buffers NOT aliased in the compiled module: "
            f"{missing} (param number, carry path) — the donation was "
            "dropped and these buffers are copied every round", None)


@register_rule("rng_advance")
def rng_advance(ctx: RuleContext) -> None:
    """A PRNG carry leaf must come back advanced: if a key-typed (or raw
    ``u32[2]`` PRNGKey) input var is returned as an output var unchanged,
    the next round replays the same bits — the PR-3 donation footgun's
    jaxpr-visible half."""
    if not ctx.check_rng_advance:
        return
    jaxpr = ctx.jaxpr.jaxpr
    out_ids = {id(v) for v in jaxpr.outvars}
    for v in jaxpr.invars:
        aval = getattr(v, "aval", None)
        if aval is None:
            continue
        is_raw_key = (getattr(aval, "shape", None) == (2,)
                      and str(getattr(aval, "dtype", "")) == "uint32")
        if (_is_key_var(v) or is_raw_key) and id(v) in out_ids:
            ctx.finding(
                "rng_advance",
                "PRNG carry leaf returned unadvanced (output var == input "
                "var): the next round replays identical random bits", None)


# --------------------------------------------------------------------- #
# 4. dtype discipline                                                   #
# --------------------------------------------------------------------- #

# reduction/contraction prims whose output dtype IS the accumulator
# dtype; a lone elementwise `add` (EF inject, params+update) is not an
# accumulation chain and stays legal in the leaf dtype
_ACCUM_PRIMS = {"reduce_sum", "dot_general", "cumsum",
                "reduce_window_sum"}
_HALF = {"bfloat16", "float16"}


@register_rule("dtype_discipline")
def dtype_discipline(ctx: RuleContext) -> None:
    """Accumulation chains stay fp32, one cast per leaf at the write.
    (a) any leaf-sized add/reduce_sum/dot_general producing a half dtype
    is a half-precision accumulation; (b) more leaf-sized fp32->half
    casts than half-precision outputs means per-slice round-trip casts
    inside the chain (the drift the fused kernels were built to avoid)."""
    if "dtype_discipline" in ctx.rules_off:
        return
    threshold = max(ctx.copy_threshold, 1)
    half_casts = []
    for j, eqn in tv.all_eqns(ctx.jaxpr):
        name = eqn.primitive.name
        if not eqn.outvars:
            continue
        aval = eqn.outvars[0].aval
        dt = str(getattr(aval, "dtype", ""))
        if _out_size(eqn) < threshold:
            continue
        if name in _ACCUM_PRIMS and dt in _HALF:
            ctx.finding(
                "dtype_discipline",
                f"half-precision accumulation: {name} -> {dt} at "
                f"{_out_size(eqn)} elems (accumulate fp32, cast at the "
                "write)", eqn)
        elif (name == "convert_element_type" and dt in _HALF
              and str(getattr(eqn.invars[0].aval, "dtype", ""))
              == "float32"):
            half_casts.append(eqn)
    n_half_out = sum(
        1 for a in ctx.jaxpr.out_avals
        if str(getattr(a, "dtype", "")) in _HALF)
    if len(half_casts) > max(n_half_out, 0) and half_casts:
        ctx.finding(
            "dtype_discipline",
            f"{len(half_casts)} leaf-sized fp32->half casts for "
            f"{n_half_out} half-precision outputs: more than one cast "
            "per leaf means mid-chain precision round-trips",
            half_casts[-1])


# --------------------------------------------------------------------- #
# 5. Pallas budget                                                      #
# --------------------------------------------------------------------- #

def _block_dims(bm) -> tuple:
    """A BlockMapping's block shape as ints: a dim is an int or a
    ``Blocked(block_size=...)``; a squeezed dim counts as 1."""
    return tuple(d if isinstance(d, int)
                 else int(getattr(d, "block_size", None) or 1)
                 for d in getattr(bm, "block_shape", ()) or ())


def _block_bytes(bm) -> int:
    shape = _block_dims(bm)
    aval = getattr(bm, "block_aval", None)
    itemsize = 4
    for attr in ("dtype", "inner_aval"):
        obj = getattr(aval, attr, None)
        if obj is None:
            continue
        dt = getattr(obj, "dtype", obj)
        itemsize = getattr(dt, "itemsize", 4)
        break
    return int(np.prod(shape or (1,))) * int(itemsize)


@register_rule("pallas_budget")
def pallas_budget(ctx: RuleContext) -> None:
    """Per-pallas_call VMEM working-set estimate: 2x (double buffering)
    the summed block bytes of all in/out BlockSpecs. Over ~75% of the
    16 MiB VMEM is an error; lane-minor block layouts (minor dim < 128
    and != 1) are emitted as notes — data for the "(C,) lane-minor"
    carry-over, not a gate."""
    if "pallas_budget" in ctx.rules_off:
        return
    for j, eqn in tv.all_eqns(ctx.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            continue
        bms = list(getattr(gm, "block_mappings", ()) or ())
        working = 2 * sum(_block_bytes(bm) for bm in bms)
        name = str(eqn.params.get("name")
                   or eqn.params.get("name_and_src_info", "pallas_call"))
        name = name.split(" ")[0]
        grid = tuple(getattr(gm, "grid", ()) or ())
        lane_minor = []
        for bm in bms:
            shape = _block_dims(bm)
            if shape and 1 < shape[-1] < LANE:
                lane_minor.append(shape)
        ctx.note(
            f"pallas kernel {name}: grid={grid} blocks={len(bms)} "
            f"vmem~{working / 1024:.0f}KiB"
            + (f" lane-minor blocks={lane_minor}" if lane_minor else ""))
        if working > VMEM_BUDGET:
            ctx.finding(
                "pallas_budget",
                f"kernel {name} VMEM working set ~{working >> 20}MiB "
                f"(2x block bytes) exceeds the {VMEM_BUDGET >> 20}MiB "
                "budget: shrink the BlockSpecs or add a grid dimension",
                eqn)


# --------------------------------------------------------------------- #
# 6. fusion count                                                       #
# --------------------------------------------------------------------- #

@register_rule("fusion_count", kind="hlo")
def fusion_count(ctx: RuleContext) -> None:
    """The aggregation path stays fused: total bytes the ENTRY
    computation's kernels write to HBM, measured in multiples of the
    cohort update payload ("HBM passes"), must stay under the entry's
    cap.  A fused trimmed-mean makes ~1 pass (the per-leaf cohort-axis
    sort) plus the 1/C-sized aggregated outputs; a flatten+copy chain,
    a re-sorted intermediate, or a dropped fusion roughly doubles the
    traffic, so the cap catches the regression class PR 2's streaming
    kernels were built to eliminate."""
    if (ctx.hbm_pass_cap is None or not ctx.hbm_payload_bytes
            or ctx.hlo_text is None):
        return
    floor = max(ctx.hbm_bytes_threshold, 1)
    mats = list(hlo_mod.iter_materializations(ctx.hlo_text,
                                              min_bytes=floor))
    total = sum(m.bytes for m in mats)
    passes = total / ctx.hbm_payload_bytes
    ctx.note(f"hbm passes: {passes:.2f}x payload ({total}B over "
             f"{len(mats)} kernels >= {floor}B, cap {ctx.hbm_pass_cap}x)")
    if passes > ctx.hbm_pass_cap:
        top = sorted(mats, key=lambda m: -m.bytes)[:3]
        ctx.finding(
            "fusion_count",
            f"aggregation path materializes {passes:.2f}x the cohort "
            f"payload in HBM ({total}B vs {ctx.hbm_payload_bytes}B "
            f"payload across {len(mats)} kernels), cap "
            f"{ctx.hbm_pass_cap}x: XLA is spilling intermediates — "
            "largest: " + "; ".join(f"{m.op}:{m.bytes}B" for m in top),
            None)


# --------------------------------------------------------------------- #
# 7. collective lint                                                    #
# --------------------------------------------------------------------- #

@register_rule("collective_lint", kind="hlo")
def collective_lint(ctx: RuleContext) -> None:
    """Per-entry collective allowlist over the compiled module: each
    collective kind's total per-chip operand bytes must stay under the
    entry's declared cap; kinds absent from the allowlist are forbidden
    outright (aggregate_sharded may psum (C,) partials + the Gram matrix
    but must never all-to-all or all-gather a param-sized operand)."""
    if ctx.collective_allowlist is None or ctx.hlo_text is None:
        return
    totals: Dict[str, int] = {}
    sample: Dict[str, hlo_mod.CollectiveOp] = {}
    for op in hlo_mod.iter_collectives(ctx.hlo_text):
        totals[op.kind] = totals.get(op.kind, 0) + op.bytes
        sample.setdefault(op.kind, op)
    for kind, total in sorted(totals.items()):
        cap = ctx.collective_allowlist.get(kind)
        if cap is None:
            ctx.finding(
                "collective_lint",
                f"forbidden collective {kind} ({total} bytes/chip): "
                f"not in this entry's allowlist "
                f"{sorted(ctx.collective_allowlist)} | "
                f"{sample[kind].line[:120]}", None)
        elif total > cap:
            ctx.finding(
                "collective_lint",
                f"{kind} moves {total} bytes/chip, allowlist caps it at "
                f"{cap}: a param-sized operand is crossing the "
                f"interconnect | {sample[kind].line[:120]}", None)
    if totals:
        ctx.note("collectives/chip: " + ", ".join(
            f"{k}={v}B" for k, v in sorted(totals.items())))


def run_rules(ctx: RuleContext) -> EntryResult:
    """Run every registered rule (minus the entry's rules_off) over one
    context; hlo-kind rules no-op when the entry was not compiled."""
    for rule in RULES.values():
        if rule.name in ctx.rules_off:
            continue
        if rule.kind == "hlo" and ctx.hlo_text is None:
            continue
        rule.fn(ctx)
    if ctx.result.findings:
        ctx.result.status = "findings"
    return ctx.result
