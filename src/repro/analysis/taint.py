"""Jaxpr taint interpreter — the dataflow layer of the DP verifier.

Walks a :class:`ClosedJaxpr` (the traced train step) propagating a
:class:`Taint` per value:

* ``batch_dims`` — which array dims carry *example identity* (the per-example
  axis and anything it permutes/reshapes into).  Seeded as dim 0 of every
  batch input; precise dimension maps for the structural / contraction
  primitives; conservative all-dims for anything unknown (``pallas_call``
  included).
* ``sensitive`` — derived (through any op) from training data.
* ``clipped`` — passed through a ``dp_mark[kind=clip]`` site (sticky).
* ``noise_ids`` — the set of ``dp_mark[kind=noise]`` draws mixed into this
  value.  A released leaf must carry exactly one.
* ``agg_unclipped`` — ids of *unclipped batch-axis eliminations* upstream:
  whenever an eqn sums/contracts away a batch-tainted dim and no operand of
  that contraction is clipped, an aggregation event is recorded and its id
  sticks to the result.  This is the "clips before it aggregates" check in a
  form that survives ghost-norm recombination (``dW = Xᵀ(coef·dY)`` is fine:
  one side of the contraction is clipped).
* ``rng`` — a hashable PRNG-key identity.  ``random_split`` / ``fold_in`` /
  ``random_bits`` *consume* their input key (recorded as an event and used
  for key-reuse detection); static slices of split outputs derive distinct
  child identities.

Sub-jaxprs (jit, scan, while, cond, custom_jvp/vjp, remat) are interpreted
recursively; scan/while carries run to a join fixpoint with event counting
disabled, then one final counting pass.

The interpreter only *collects*; :mod:`repro.analysis.rules` turns the
collected state into violations.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var
from jax.extend.core import primitives as _prims

try:                                    # readable "prim @ file:line" provenance
    from jax._src import source_info_util

    def _src_of(eqn) -> str:
        try:
            return source_info_util.summarize(eqn.source_info)
        except Exception:
            return "?"
except Exception:                        # pragma: no cover - jax internals moved
    def _src_of(eqn) -> str:
        return "?"


def eqn_summary(eqn) -> str:
    prim = eqn.primitive.name
    if prim == "dp_mark":
        prim = f"dp_mark[kind={eqn.params.get('kind')}]"
    outs = ", ".join(str(getattr(v, "aval", "?")) for v in eqn.outvars[:2])
    return f"{prim} -> ({outs}) @ {_src_of(eqn)}"


# ---------------------------------------------------------------------------
# Taint lattice
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Taint:
    batch_dims: FrozenSet[int] = frozenset()
    sensitive: bool = False
    clipped: bool = False
    noise_ids: FrozenSet[int] = frozenset()
    agg_unclipped: FrozenSet[int] = frozenset()
    rng: Any = None
    src: str = ""

    def clean(self) -> bool:
        return (not self.batch_dims and not self.sensitive and not self.clipped
                and not self.noise_ids and not self.agg_unclipped
                and self.rng is None)

    def with_dims(self, dims: FrozenSet[int], src: str = "") -> "Taint":
        return dataclasses.replace(self, batch_dims=frozenset(dims),
                                   src=src or self.src)


CLEAN = Taint()


def join(a: Taint, b: Taint) -> Taint:
    """Least upper bound (used for scan/while/cond joins)."""
    return Taint(
        batch_dims=a.batch_dims | b.batch_dims,
        sensitive=a.sensitive or b.sensitive,
        clipped=a.clipped or b.clipped,
        noise_ids=a.noise_ids | b.noise_ids,
        agg_unclipped=a.agg_unclipped | b.agg_unclipped,
        rng=a.rng if a.rng == b.rng else None,
        src=a.src or b.src,
    )


def _union(ins: Sequence[Taint], dims: FrozenSet[int], src: str,
           rng: Any = None) -> Taint:
    return Taint(
        batch_dims=frozenset(dims),
        sensitive=any(t.sensitive for t in ins),
        clipped=any(t.clipped for t in ins),
        noise_ids=frozenset().union(*(t.noise_ids for t in ins)) if ins else frozenset(),
        agg_unclipped=frozenset().union(*(t.agg_unclipped for t in ins)) if ins else frozenset(),
        rng=rng,
        src=src,
    )


# ---------------------------------------------------------------------------
# Collected global state
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NoiseMark:
    mark_id: int
    scale: Optional[float]
    in_taint: Taint
    src: str


@dataclasses.dataclass
class ReleaseMark:
    in_taint: Taint
    src: str


@dataclasses.dataclass
class AggEvent:
    """A batch-axis elimination.  ``clipped`` is True when some operand of the
    eliminating contraction passed through a clip site."""
    event_id: int
    clipped: bool
    src: str


@dataclasses.dataclass
class RngEvent:
    key_id: Any
    prim: str
    src: str
    loop_const: bool = False    # consumed a loop-invariant key inside scan/while


@dataclasses.dataclass
class JoinEvent:
    """A noised value met a sensitive, not-yet-noised operand — the noise
    application point.  ``other`` is that operand's taint."""
    other: Taint
    src: str


@dataclasses.dataclass
class TaintResult:
    out_taints: List[Taint]
    noise_marks: List[NoiseMark] = dataclasses.field(default_factory=list)
    release_marks: List[ReleaseMark] = dataclasses.field(default_factory=list)
    agg_events: Dict[int, AggEvent] = dataclasses.field(default_factory=dict)
    rng_events: List[RngEvent] = dataclasses.field(default_factory=list)
    join_events: List[JoinEvent] = dataclasses.field(default_factory=list)
    clip_sites: List[str] = dataclasses.field(default_factory=list)
    unknown_prims: Dict[str, int] = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# Dim-map helpers
# ---------------------------------------------------------------------------

def _shape_of(var) -> Tuple[int, ...]:
    aval = getattr(var, "aval", None)
    return tuple(getattr(aval, "shape", ()) or ())


def _reshape_dim_spans(shape: Sequence[int]):
    """Per dim, the (lo, hi) multiplicative stride interval in flat index
    space: dim d spans [prod(shape[d+1:]), prod(shape[d:]))."""
    spans = []
    period = 1
    for size in reversed([int(s) for s in shape]):
        spans.append((period, period * size))
        period *= size
    spans.reverse()
    return spans


def map_reshape_dims(in_shape, out_shape, dims: FrozenSet[int]) -> FrozenSet[int]:
    """Which output dims a set of input dims can alias after a reshape —
    dims interact iff their flat-stride intervals overlap (size-1 dims never
    do, so singleton axes drop out for free)."""
    in_spans = _reshape_dim_spans(in_shape)
    out_spans = _reshape_dim_spans(out_shape)
    out: set = set()
    for d in dims:
        if d >= len(in_spans):
            continue
        lo, hi = in_spans[d]
        if lo == hi:
            continue
        for e, (elo, ehi) in enumerate(out_spans):
            if elo != ehi and max(lo, elo) < min(hi, ehi):
                out.add(e)
    return frozenset(out)


def _shift_dims(dims: FrozenSet[int], removed: Sequence[int]) -> FrozenSet[int]:
    removed = sorted(set(int(a) for a in removed))
    out = set()
    for d in dims:
        if d in removed:
            continue
        out.add(d - sum(1 for a in removed if a < d))
    return frozenset(out)


# ---------------------------------------------------------------------------
# Interpreter
# ---------------------------------------------------------------------------

Handler = Callable[["Interpreter", Any, List[Taint], bool], List[Taint]]

HANDLERS: Dict[str, Handler] = {}


def handler(*names: str):
    def deco(fn: Handler) -> Handler:
        for n in names:
            HANDLERS[n] = fn
        return fn
    return deco


# primitives that recurse but whose jaxpr param names differ
_CALL_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


class Interpreter:
    def __init__(self) -> None:
        self._ids = itertools.count(1)
        self.result: TaintResult = TaintResult(out_taints=[])
        # rng ids that are loop-invariant w.r.t. the innermost loop body
        self._loop_const_rng: List[FrozenSet[Any]] = []

    # -- public entry -------------------------------------------------------

    def run(self, closed: ClosedJaxpr, in_taints: Sequence[Taint]) -> TaintResult:
        outs = self.eval_jaxpr(closed.jaxpr, list(in_taints), count=True)
        self.result.out_taints = outs
        return self.result

    # -- helpers ------------------------------------------------------------

    def fresh_id(self) -> int:
        return next(self._ids)

    def record_agg(self, clipped: bool, src: str, count: bool) -> FrozenSet[int]:
        """Record a batch-axis elimination; returns the id set to attach to
        the result (empty when the contraction is clipped)."""
        if clipped or not count:
            return frozenset()
        eid = self.fresh_id()
        self.result.agg_events[eid] = AggEvent(eid, clipped, src)
        return frozenset([eid])

    def _in_loop_const(self, key_id: Any) -> bool:
        return any(key_id in s for s in self._loop_const_rng)

    def consume_rng(self, taint: Taint, prim: str, src: str, count: bool) -> None:
        if taint.rng is None or not count:
            return
        self.result.rng_events.append(RngEvent(
            key_id=taint.rng, prim=prim, src=src,
            loop_const=(prim in ("random_split", "random_bits", "threefry2x32")
                        and self._in_loop_const(taint.rng)),
        ))

    # -- core loop ----------------------------------------------------------

    def eval_jaxpr(self, jaxpr: Jaxpr, in_taints: List[Taint], *,
                   count: bool) -> List[Taint]:
        env: Dict[Var, Taint] = {}

        def read(atom) -> Taint:
            if isinstance(atom, Literal):
                return CLEAN
            return env.get(atom, CLEAN)

        def write(var, taint: Taint) -> None:
            if type(var).__name__ == "DropVar":
                return
            env[var] = taint

        for cv in jaxpr.constvars:
            write(cv, CLEAN)
        if len(in_taints) != len(jaxpr.invars):
            raise ValueError(
                f"taint/invars mismatch: {len(in_taints)} taints for "
                f"{len(jaxpr.invars)} invars")
        for v, t in zip(jaxpr.invars, in_taints):
            write(v, t)

        for eqn in jaxpr.eqns:
            ins = [read(x) for x in eqn.invars]
            src = eqn_summary(eqn)
            # a noised value meeting sensitive-un-noised material is where the
            # noise is *applied* — record the other operand for the rules
            if count and any(t.noise_ids for t in ins):
                for t in ins:
                    if t.sensitive and not t.noise_ids:
                        self.result.join_events.append(JoinEvent(t, src))
            fn = HANDLERS.get(eqn.primitive.name, _default_rule)
            outs = fn(self, eqn, ins, count)
            if len(outs) != len(eqn.outvars):
                raise AssertionError(
                    f"handler for {eqn.primitive.name} returned {len(outs)} "
                    f"taints for {len(eqn.outvars)} outvars")
            for v, t in zip(eqn.outvars, outs):
                write(v, t)

        return [read(x) for x in jaxpr.outvars]

    def eval_closed(self, closed: ClosedJaxpr, in_taints: List[Taint], *,
                    count: bool) -> List[Taint]:
        return self.eval_jaxpr(closed.jaxpr, in_taints, count=count)


# ---------------------------------------------------------------------------
# Default rule
# ---------------------------------------------------------------------------

def _default_rule(interp: Interpreter, eqn, ins: List[Taint],
                  count: bool) -> List[Taint]:
    """No registered handler.  Equal-rank inputs map dims identically
    (covers every elementwise/select/cumulative/sort-ish primitive); anything
    else is conservative: if a batch-tainted input exists, every output dim
    is tainted (no elimination event is recorded — the taint survives, so a
    bad flow is still caught downstream, just less precisely)."""
    name = eqn.primitive.name
    out_taints = []
    for ov in eqn.outvars:
        out_shape = _shape_of(ov)
        dims: set = set()
        conservative = False
        for iv, t in zip(eqn.invars, ins):
            if not t.batch_dims:
                continue
            in_shape = _shape_of(iv)
            if len(in_shape) == len(out_shape):
                dims |= set(d for d in t.batch_dims if d < len(out_shape))
            else:
                conservative = True
        if conservative:
            dims = set(range(len(out_shape)))
            interp.result.unknown_prims[name] = (
                interp.result.unknown_prims.get(name, 0) + 1)
        out_taints.append(_union(ins, frozenset(dims), eqn_summary(eqn)))
    return out_taints


# ---------------------------------------------------------------------------
# dp_mark
# ---------------------------------------------------------------------------

@handler("dp_mark")
def _mark_rule(interp, eqn, ins, count):
    (t,) = ins
    kind = eqn.params["kind"]
    src = eqn_summary(eqn)
    if kind == "clip":
        if count:
            interp.result.clip_sites.append(src)
        dims = frozenset() if eqn.params.get("aggregated") else t.batch_dims
        return [dataclasses.replace(t, clipped=True, batch_dims=dims, src=src)]
    if kind == "noise":
        mid = interp.fresh_id()
        if count:
            interp.result.noise_marks.append(
                NoiseMark(mid, eqn.params.get("scale"), t, src))
        return [dataclasses.replace(t, noise_ids=t.noise_ids | {mid}, src=src)]
    if kind == "release":
        if count:
            interp.result.release_marks.append(ReleaseMark(t, src))
        return [t]
    raise ValueError(f"unknown dp_mark kind {kind!r}")


# ---------------------------------------------------------------------------
# Structural primitives (precise dim maps)
# ---------------------------------------------------------------------------

@handler("broadcast_in_dim")
def _broadcast_rule(interp, eqn, ins, count):
    (t,) = ins
    bcast = eqn.params["broadcast_dimensions"]
    dims = frozenset(bcast[d] for d in t.batch_dims if d < len(bcast))
    return [t.with_dims(dims, eqn_summary(eqn))]


@handler("transpose")
def _transpose_rule(interp, eqn, ins, count):
    (t,) = ins
    perm = eqn.params["permutation"]
    dims = frozenset(i for i, p in enumerate(perm) if p in t.batch_dims)
    return [t.with_dims(dims, eqn_summary(eqn))]


@handler("reshape")
def _reshape_rule(interp, eqn, ins, count):
    (t,) = ins
    if eqn.params.get("dimensions") is not None:     # fused transpose: rare
        return _default_rule(interp, eqn, ins, count)
    in_shape = _shape_of(eqn.invars[0])
    out_shape = _shape_of(eqn.outvars[0])
    dims = map_reshape_dims(in_shape, out_shape, t.batch_dims)
    return [t.with_dims(dims, eqn_summary(eqn))]


@handler("squeeze")
def _squeeze_rule(interp, eqn, ins, count):
    (t,) = ins
    dims = _shift_dims(t.batch_dims, eqn.params["dimensions"])
    return [dataclasses.replace(t, batch_dims=dims, src=eqn_summary(eqn))]


@handler("slice")
def _slice_rule(interp, eqn, ins, count):
    (t,) = ins
    rng = None
    if t.rng is not None:       # distinct static slices -> distinct child keys
        rng = (t.rng, ("slice", tuple(int(s) for s in eqn.params["start_indices"]),
                       tuple(int(s) for s in eqn.params["limit_indices"])))
    return [dataclasses.replace(t, rng=rng, src=eqn_summary(eqn))]


@handler("concatenate")
def _concat_rule(interp, eqn, ins, count):
    out_rank = len(_shape_of(eqn.outvars[0]))
    dims = frozenset().union(*(t.batch_dims for t in ins)) if ins else frozenset()
    dims = frozenset(d for d in dims if d < out_rank)
    return [_union(ins, dims, eqn_summary(eqn))]


@handler("dynamic_slice")
def _dynslice_rule(interp, eqn, ins, count):
    t = ins[0]
    rng = None
    if t.rng is not None:
        rng = (t.rng, ("dynslice", interp.fresh_id()))
    out = _union(ins, t.batch_dims, eqn_summary(eqn), rng=rng)
    return [out]


@handler("dynamic_update_slice")
def _dynupdate_rule(interp, eqn, ins, count):
    operand, update = ins[0], ins[1]
    dims = operand.batch_dims | update.batch_dims
    return [_union(ins, dims, eqn_summary(eqn))]


# ---------------------------------------------------------------------------
# Reductions / contractions (aggregation events live here)
# ---------------------------------------------------------------------------

def _reduce_like(interp, eqn, ins, count, axes):
    t = ins[0]
    src = eqn_summary(eqn)
    agg: FrozenSet[int] = frozenset()
    if t.sensitive and any(a in t.batch_dims for a in axes):
        agg = interp.record_agg(any(x.clipped for x in ins), src, count)
    dims = _shift_dims(t.batch_dims, axes)
    out = _union(ins, dims, src)
    return [dataclasses.replace(out, agg_unclipped=out.agg_unclipped | agg)
            for _ in eqn.outvars]


@handler("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
         "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin")
def _reduce_rule(interp, eqn, ins, count):
    return _reduce_like(interp, eqn, ins, count, eqn.params["axes"])


@handler("dot_general")
def _dot_rule(interp, eqn, ins, count):
    lhs, rhs = ins[0], ins[1]
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    src = eqn_summary(eqn)
    lhs_rank = len(_shape_of(eqn.invars[0]))
    rhs_rank = len(_shape_of(eqn.invars[1]))
    lhs_free = [d for d in range(lhs_rank) if d not in lc and d not in lb]
    rhs_free = [d for d in range(rhs_rank) if d not in rc and d not in rb]

    dims: set = set()
    for d in lhs.batch_dims:
        if d in lb:
            dims.add(list(lb).index(d))
        elif d in lhs_free:
            dims.add(len(lb) + lhs_free.index(d))
    for d in rhs.batch_dims:
        if d in rb:
            dims.add(list(rb).index(d))
        elif d in rhs_free:
            dims.add(len(lb) + len(lhs_free) + rhs_free.index(d))

    agg: FrozenSet[int] = frozenset()
    contracted = (any(d in lc for d in lhs.batch_dims) or
                  any(d in rc for d in rhs.batch_dims))
    if contracted and (lhs.sensitive or rhs.sensitive):
        # Xᵀ(coef·dY): clipped if EITHER side of the contraction is clipped
        agg = interp.record_agg(lhs.clipped or rhs.clipped, src, count)
    out = _union(ins, frozenset(dims), src)
    return [dataclasses.replace(out, agg_unclipped=out.agg_unclipped | agg)]


# ---------------------------------------------------------------------------
# Gather / scatter
# ---------------------------------------------------------------------------

@handler("gather")
def _gather_rule(interp, eqn, ins, count):
    operand, indices = ins[0], ins[1]
    d = eqn.params["dimension_numbers"]
    out_rank = len(_shape_of(eqn.outvars[0]))
    idx_rank = len(_shape_of(eqn.invars[1]))
    offset_dims = list(d.offset_dims)
    collapsed = set(d.collapsed_slice_dims)
    op_batching = list(getattr(d, "operand_batching_dims", ()) or ())
    idx_batching = list(getattr(d, "start_indices_batching_dims", ()) or ())
    batch_out = [i for i in range(out_rank) if i not in offset_dims]
    # jax's gather convention: the index vector is ALWAYS the last indices dim
    idx_dims = list(range(idx_rank - 1))

    dims: set = set()
    # operand window dims (not collapsed, not batching) map in order onto the
    # offset dims of the output
    op_rank = len(_shape_of(eqn.invars[0]))
    surviving = [od for od in range(op_rank)
                 if od not in collapsed and od not in op_batching]
    for dd in operand.batch_dims:
        if dd in surviving and surviving.index(dd) < len(offset_dims):
            dims.add(offset_dims[surviving.index(dd)])
    # indices dims (minus the index-vector dim) map to the non-offset out dims
    for dd in indices.batch_dims:
        if dd in idx_dims and idx_dims.index(dd) < len(batch_out):
            dims.add(batch_out[idx_dims.index(dd)])
    # batching dims (vmapped gather): operand dim ob[k] is locked to indices
    # dim sib[k], whose output position is its slot among the non-offset dims
    for ob, sib in zip(op_batching, idx_batching):
        if ob in operand.batch_dims and sib in idx_dims:
            pos = idx_dims.index(sib)
            if pos < len(batch_out):
                dims.add(batch_out[pos])
    return [_union(ins, frozenset(dims), eqn_summary(eqn))]


@handler("scatter", "scatter-add", "scatter_add", "scatter-mul", "scatter-min",
         "scatter-max", "scatter_sub")
def _scatter_rule(interp, eqn, ins, count):
    operand, indices, updates = ins[0], ins[1], ins[2]
    d = eqn.params["dimension_numbers"]
    op_rank = len(_shape_of(eqn.invars[0]))
    idx_rank = len(_shape_of(eqn.invars[1]))
    upd_rank = len(_shape_of(eqn.invars[2]))
    inserted = set(d.inserted_window_dims)
    op_batching = list(getattr(d, "operand_batching_dims", ()) or ())
    idx_batching = list(getattr(d, "scatter_indices_batching_dims", ()) or ())
    operand_window = [od for od in range(op_rank)
                      if od not in inserted and od not in op_batching]
    uwd = list(d.update_window_dims)
    # updates' non-window (scatter) dims align in order with the indices dims
    # minus the trailing index-vector dim
    upd_scatter = [ud for ud in range(upd_rank) if ud not in uwd]
    idx_dims = list(range(idx_rank - 1))

    dims = set(dd for dd in operand.batch_dims)
    # update window dims map (in order) onto the operand's window dims; the
    # updates' unbatched scatter dims (example axis under vmap-of-grad)
    # intentionally DON'T map anywhere — documented under-taint, kept
    # conservative-safe because every such flow is re-tainted at the
    # clip-coefficient multiply
    for i, ud in enumerate(uwd):
        if ud in updates.batch_dims and i < len(operand_window):
            dims.add(operand_window[i])
    # batching dims (vmapped scatter): operand dim ob[k] is locked to indices
    # dim sib[k] and to the matching updates scatter dim — taint flows through
    for ob, sib in zip(op_batching, idx_batching):
        if sib not in idx_dims:
            continue
        pos = idx_dims.index(sib)
        upd_dim = upd_scatter[pos] if pos < len(upd_scatter) else None
        if sib in indices.batch_dims or (upd_dim is not None
                                         and upd_dim in updates.batch_dims):
            dims.add(ob)
    return [_union(ins, frozenset(dims), eqn_summary(eqn))]


# ---------------------------------------------------------------------------
# RNG primitives
# ---------------------------------------------------------------------------

@handler("random_seed")
def _random_seed_rule(interp, eqn, ins, count):
    return [dataclasses.replace(
        _union(ins, frozenset(), eqn_summary(eqn)), rng=interp.fresh_id())]


@handler("random_wrap", "random_unwrap")
def _random_wrap_rule(interp, eqn, ins, count):
    (t,) = ins
    return [dataclasses.replace(t, batch_dims=frozenset(),
                                src=eqn_summary(eqn))]


@handler("random_split")
def _random_split_rule(interp, eqn, ins, count):
    (t,) = ins
    src = eqn_summary(eqn)
    interp.consume_rng(t, "random_split", src, count)
    return [dataclasses.replace(t, rng=interp.fresh_id(),
                                batch_dims=frozenset(), src=src)]


@handler("random_fold_in")
def _random_fold_rule(interp, eqn, ins, count):
    t = ins[0]
    src = eqn_summary(eqn)
    interp.consume_rng(t, "random_fold_in", src, count)
    out = _union(ins, frozenset(), src, rng=interp.fresh_id())
    return [out]


@handler("random_bits", "threefry2x32", "random_gamma")
def _random_bits_rule(interp, eqn, ins, count):
    src = eqn_summary(eqn)
    for t in ins:
        interp.consume_rng(t, "random_bits", src, count)
    return [_union(ins, frozenset(), src) for _ in eqn.outvars]


# ---------------------------------------------------------------------------
# Sub-jaxpr primitives
# ---------------------------------------------------------------------------

def _find_sub_jaxpr(params) -> Optional[ClosedJaxpr]:
    for k in _CALL_JAXPR_PARAMS:
        sub = params.get(k)
        if sub is None:
            continue
        if isinstance(sub, ClosedJaxpr):
            return sub
        if isinstance(sub, Jaxpr):
            return ClosedJaxpr(sub, [])
    return None


# the call-like primitives that carry a sub-jaxpr, under the names the
# installed jax gives them (a nested jit is "jit", jax.checkpoint "remat2").
# shard_map's body keeps the global dims' positions (each device sees a
# slice of them), so it interprets like a call; jax.extend does not export
# its primitive
_CALL_PRIMITIVES = tuple(p.name for p in (
    _prims.jit_p, _prims.closed_call_p, _prims.call_p, _prims.remat_p,
    _prims.custom_jvp_call_p, _prims.custom_vjp_call_p)) + ("shard_map",)


@handler(*_CALL_PRIMITIVES)
def _call_rule(interp, eqn, ins, count):
    sub = _find_sub_jaxpr(eqn.params)
    if sub is None:
        return _default_rule(interp, eqn, ins, count)
    n = len(sub.jaxpr.invars)
    # custom_* calls pass extra leading args (the fun itself consumes the
    # first n of the eqn's invars in order); align from the tail when the
    # counts disagree.
    args = ins[:n] if len(ins) >= n else ins + [CLEAN] * (n - len(ins))
    if len(ins) > n:
        args = ins[len(ins) - n:]
    return interp.eval_closed(sub, list(args), count=count)


@handler("scan")
def _scan_rule(interp, eqn, ins, count):
    p = eqn.params
    closed: ClosedJaxpr = p["jaxpr"]
    nc, nk = p["num_consts"], p["num_carry"]
    consts, carries, xs = ins[:nc], ins[nc:nc + nk], ins[nc + nk:]

    scan_axis_tainted = any(0 in t.batch_dims for t in xs)
    xs_body = [dataclasses.replace(t, batch_dims=_shift_dims(t.batch_dims, (0,)))
               for t in xs]

    loop_rng = frozenset(t.rng for t in consts if t.rng is not None)
    interp._loop_const_rng.append(loop_rng)
    try:
        carry_t = list(carries)
        for _ in range(8):                       # fixpoint, counting off
            outs = interp.eval_closed(closed, consts + carry_t + xs_body,
                                      count=False)
            new_carry = [join(a, b) for a, b in zip(carry_t, outs[:nk])]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        outs = interp.eval_closed(closed, consts + carry_t + xs_body,
                                  count=count)
    finally:
        interp._loop_const_rng.pop()

    src = eqn_summary(eqn)
    carry_out = [dataclasses.replace(join(a, b), src=src)
                 for a, b in zip(carry_t, outs[:nk])]
    ys_out = []
    for t in outs[nk:]:
        dims = frozenset(d + 1 for d in t.batch_dims)
        if scan_axis_tainted and t.sensitive:
            dims = dims | {0}
        ys_out.append(dataclasses.replace(t, batch_dims=dims, src=src))
    return carry_out + ys_out


@handler("while")
def _while_rule(interp, eqn, ins, count):
    p = eqn.params
    cond_n, body_n = p["cond_nconsts"], p["body_nconsts"]
    body: ClosedJaxpr = p["body_jaxpr"]
    body_consts = ins[cond_n:cond_n + body_n]
    carries = ins[cond_n + body_n:]

    loop_rng = frozenset(t.rng for t in body_consts if t.rng is not None)
    interp._loop_const_rng.append(loop_rng)
    try:
        carry_t = list(carries)
        for _ in range(8):
            outs = interp.eval_closed(body, body_consts + carry_t, count=False)
            new_carry = [join(a, b) for a, b in zip(carry_t, outs)]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        outs = interp.eval_closed(body, body_consts + carry_t, count=count)
    finally:
        interp._loop_const_rng.pop()
    src = eqn_summary(eqn)
    return [dataclasses.replace(join(a, b), src=src)
            for a, b in zip(carry_t, outs)]


@handler("cond")
def _cond_rule(interp, eqn, ins, count):
    branches = eqn.params["branches"]
    ops = ins[1:]
    per_branch = [interp.eval_closed(br, list(ops), count=count)
                  for br in branches]
    src = eqn_summary(eqn)
    outs = []
    for vals in zip(*per_branch):
        t = vals[0]
        for v in vals[1:]:
            t = join(t, v)
        outs.append(dataclasses.replace(t, src=src))
    return outs


# ---------------------------------------------------------------------------
# Opaque compute (Pallas etc.) — fully conservative
# ---------------------------------------------------------------------------

@handler("pallas_call")
def _pallas_rule(interp, eqn, ins, count):
    src = eqn_summary(eqn)
    tainted = any(t.batch_dims for t in ins)
    outs = []
    for ov in eqn.outvars:
        rank = len(_shape_of(ov))
        dims = frozenset(range(rank)) if tainted else frozenset()
        outs.append(_union(ins, dims, src))
    return outs


# identity-ish ops where the default equal-rank rule is right but we also
# want to preserve rng identity through them
@handler("convert_element_type", "reduce_precision", "copy",
         "sharding_constraint", "device_put")
def _identityish_rule(interp, eqn, ins, count):
    outs = _default_rule(interp, eqn, ins, count)
    if len(ins) == 1 and ins[0].rng is not None:
        outs = [dataclasses.replace(t, rng=ins[0].rng) for t in outs]
    return outs


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def interpret(closed: ClosedJaxpr, in_taints: Sequence[Taint]) -> TaintResult:
    """Run the taint interpreter over a closed jaxpr."""
    return Interpreter().run(closed, in_taints)
