"""Jaxpr passes: walk the closed jaxprs of the full/delta/fused beats.

Three analyzers, generalizing the hand-built proofs of
``tests/test_sharding_locality.py`` from one picked configuration to
ANY (plan, backend, shard count):

  * collective detector — a delta beat is shard-local by construction:
    its jaxpr (recursively, through shard_map / cond / pallas_call
    bodies) contains ZERO collective primitives; the full/reseed beat
    contains exactly one ``all_gather`` per mirrored predicated scan
    stage, over that stage's per-shard row slice.
  * width classifier — steady state never pays window width: no
    ``ge``/``le`` range-compare (scan) or full-spine ``eq`` probe
    (join) of a forbidden (rows, q_window) shape is reachable on the
    delta path.  Shapes that a LEGITIMATE kernel also produces (pane
    compares, dirty-row re-evals, key-locate scans) are subtracted
    first; a forbidden shape that collides with a legitimate one is
    reported as an info-severity ambiguity instead of a false error.
  * donation/alias checker — parses the lowered StableHLO's
    ``tf.aliasing_output`` markers to recover which top-level arguments
    actually donate, and flags donation of any argument reachable
    through a non-donated alias (the rid carry doubles as the previous
    beat's in-flight ``results["_join_rids"]`` — the PR-4 bug class).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import jax
import jax.extend.core as jcore

from repro.analysis_static.diagnostics import LintFinding
from repro.analysis_static import registry as R
from repro.analysis_static.registry import register_pass

COLLECTIVES = {"all_gather", "psum", "ppermute", "all_to_all", "pgather",
               "reduce_scatter", "pmax", "pmin", "pargmax", "pargmin",
               "pbroadcast"}
HLO_COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
                   "all-to-all", "reduce-scatter", "collective-broadcast")


def walk_eqns(closed):
    """Yield every eqn in a closed jaxpr, recursing into sub-jaxprs
    (shard_map / scan / cond / pallas_call bodies)."""
    def walk(jx):
        for e in jx.eqns:
            yield e
            for v in e.params.values():
                vs = v if isinstance(v, (list, tuple)) else (v,)
                for w in vs:
                    if isinstance(w, jcore.ClosedJaxpr):
                        yield from walk(w.jaxpr)
                    elif isinstance(w, jcore.Jaxpr):
                        yield from walk(w)
    yield from walk(closed.jaxpr)


def collective_names(closed) -> Set[str]:
    return {e.primitive.name for e in walk_eqns(closed)} & COLLECTIVES


# ---------------------------------------------------------------------------
# Collective detector
# ---------------------------------------------------------------------------


@register_pass("delta-collectives", "jaxpr", (R.JAXPR_DELTA_COLLECTIVE,),
               "delta beats contain zero collective primitives")
def lint_delta_collectives(closed, location: str = "delta"
                           ) -> List[LintFinding]:
    hits = collective_names(closed)
    if hits:
        return [LintFinding(
            R.JAXPR_DELTA_COLLECTIVE,
            f"collective primitives on the delta path: {sorted(hits)} "
            "— delta beats must be shard-local", location=location)]
    return []


def lint_delta_hlo(hlo_text: str, location: str = "delta"
                   ) -> List[LintFinding]:
    """Same proof on the OPTIMIZED compiled HLO (GSPMD must not have
    added a collective behind the jaxpr's back)."""
    hits = [t for t in HLO_COLLECTIVES if t in hlo_text]
    if hits:
        return [LintFinding(
            R.JAXPR_DELTA_COLLECTIVE,
            f"collective instructions in the compiled delta HLO: {hits}",
            location=location)]
    return []


@register_pass("reseed-collectives", "jaxpr", (R.JAXPR_RESEED_COLLECTIVE,),
               "reseed = one all_gather per mirrored predicated stage")
def lint_reseed_collectives(closed, lowered, spec,
                            location: str = "full") -> List[LintFinding]:
    """The full/reseed beat's only collective is one ``all_gather`` per
    mirrored predicated scan stage, each gathering that stage's
    per-shard row slice — the rescan touched every shard exactly once
    before re-assembly."""
    out = []
    names = collective_names(closed)
    mi_pred = [st for st in lowered.scans
               if spec.is_mirrored(st.table) and st.cols]
    if names - {"all_gather"}:
        out.append(LintFinding(
            R.JAXPR_RESEED_COLLECTIVE,
            f"unexpected collectives on the reseed path: "
            f"{sorted(names - {'all_gather'})}", location=location))
    gathers = [e for e in walk_eqns(closed)
               if e.primitive.name == "all_gather"]
    if len(gathers) != len(mi_pred):
        out.append(LintFinding(
            R.JAXPR_RESEED_COLLECTIVE,
            f"{len(gathers)} all_gathers != {len(mi_pred)} mirrored "
            "predicated scan stages", location=location))
        return out
    got = sorted(tuple(e.invars[0].aval.shape) for e in gathers)
    want = sorted((spec.shard_rows[st.table], st.whi - st.wlo)
                  for st in mi_pred)
    if got != want:
        out.append(LintFinding(
            R.JAXPR_RESEED_COLLECTIVE,
            f"all_gather operand shapes {got} != per-shard stage "
            f"slices {want}", location=location))
    return out


# ---------------------------------------------------------------------------
# Width classifier
# ---------------------------------------------------------------------------


def _row_candidates(lowered, table: str, spec=None) -> Set[int]:
    """Row extents a compare over ``table`` could legitimately carry:
    the schema capacity, and under a mesh the padded / per-shard
    extents."""
    cap = lowered.plan.catalog.schemas[table].capacity
    cands = {cap}
    if spec is not None:
        cands.add(spec.padded.get(table, cap))
        cands.add(spec.shard_rows.get(table, cap))
    return cands


def _width_shape_sets(lowered, spec=None
                      ) -> Tuple[Dict[Tuple[int, int], str],
                                 Set[Tuple[int, int]]]:
    """(forbidden shapes -> stage location, legitimate shapes).

    Forbidden: a range compare at (table rows, FULL stage q_window) for
    any stage whose pane is narrower than its window — the full-rescan
    work shape, unreachable from a delta beat.  Legitimate: admission
    pane compares (rows, 32*delta_words), single-row / dirty-set
    re-evals, and the storage update path's key-locate scans.  A
    forbidden shape also in the legitimate set cannot be classified
    statically and is skipped (reported as info by the caller).
    """
    cat = lowered.plan.catalog
    legit: Set[Tuple[int, int]] = set()
    for st in lowered.scans:
        if not st.cols:
            continue
        pane = 32 * st.delta_words
        for rows in _row_candidates(lowered, st.table, spec):
            legit.add((rows, pane))
        dirty = cat.schemas[st.table].dirty_cap
        legit.add((dirty, st.q_window))      # chained dirty re-eval
        legit.add((1, st.q_window))          # fused DIRTY program row
    forbidden: Dict[Tuple[int, int], str] = {}
    for st in lowered.scans:
        if not st.cols or 32 * st.delta_words >= st.q_window:
            continue                          # pane IS the window: exempt
        for rows in _row_candidates(lowered, st.table, spec):
            forbidden[(rows, st.q_window)] = f"scan[{st.table}]"
    return forbidden, legit


def _probe_shape_sets(lowered, spec=None, update_slots=None
                      ) -> Tuple[Dict[Tuple[int, int], str],
                                 Set[Tuple[int, int]]]:
    """Same split for join probes on the delta-join path: a full-probe
    ``eq`` pane is (spine rows, bucket width); the delta path probes
    only (dirty rows, one bucket).  The storage update path's
    key-locate scans on index-less PK tables ((update slots, table
    rows) ``eq``s) run on EVERY beat and are legitimate."""
    cat = lowered.plan.catalog
    legit: Set[Tuple[int, int]] = set()
    forbidden: Dict[Tuple[int, int], str] = {}
    if update_slots is not None:
        for t, schema in cat.schemas.items():
            if schema.pk and not schema.indexed:
                for rows in _row_candidates(lowered, t, spec):
                    legit.add((update_slots.n_update, rows))
                    legit.add((update_slots.n_delete, rows))
    for j in lowered.joins:
        if j.kind == "gather":
            continue
        spine_rows = _row_candidates(lowered, j.spine, spec)
        dirty = cat.schemas[j.spine].dirty_cap
        if j.kind == "partitioned":
            widths = {j.bucket_cap}
        else:                                 # block: full PK pane
            widths = _row_candidates(lowered, j.pk_table, spec)
        for w in widths:
            legit.add((dirty, w))            # chained delta probe
            legit.add((1, w))                # fused PROBE program row
            for rows in spine_rows:
                forbidden[(rows, w)] = f"join[{j.spine}->{j.pk_table}]"
    return forbidden, legit


@register_pass("delta-width", "jaxpr", (R.JAXPR_DELTA_WIDTH,),
               "no full-window compare/probe on the delta path")
def lint_delta_width(closed, lowered, spec=None, *,
                     delta_joins: bool = False, update_slots=None,
                     location: str = "delta") -> List[LintFinding]:
    """No full-window range compare (and, on the delta-join flavour, no
    full-spine probe) is reachable on the delta path."""
    out = []
    forbidden, legit = _width_shape_sets(lowered, spec)
    prims = {"ge", "le"}
    if delta_joins:
        pf, pl_ = _probe_shape_sets(lowered, spec, update_slots)
        for shape, loc in pf.items():
            forbidden.setdefault(shape, loc)
        legit |= pl_
        prims.add("eq")
    ambiguous = set(forbidden) & legit
    for shape in sorted(ambiguous):
        out.append(LintFinding(
            R.JAXPR_DELTA_WIDTH,
            f"shape {shape} is both a full-window and a legitimate "
            "delta compare at this scale — not statically classifiable",
            severity="info", location=forbidden[shape]))
    check = {s: loc for s, loc in forbidden.items()
             if s not in ambiguous}
    hits: Dict[Tuple[int, int], int] = {}
    for e in walk_eqns(closed):
        if e.primitive.name not in prims:
            continue
        shape = tuple(e.outvars[0].aval.shape)
        if len(shape) == 2 and shape in check:
            hits[shape] = hits.get(shape, 0) + 1
    for shape, n in sorted(hits.items()):
        out.append(LintFinding(
            R.JAXPR_DELTA_WIDTH,
            f"{n} full-window compare(s) of shape {shape} reachable "
            "on the delta path", location=f"{location} {check[shape]}"))
    return out


# ---------------------------------------------------------------------------
# Donation / alias checker
# ---------------------------------------------------------------------------

_ALIAS_RE = re.compile(r"%arg(\d+):[^%]*?tf\.aliasing_output")


def donated_leaf_args(fn, args: Sequence, donate_argnums: Iterable[int]
                      ) -> Set[int]:
    """Flat (leaf) argument indices the lowered StableHLO actually
    marks as donated (``tf.aliasing_output``)."""
    import warnings
    j = jax.jit(fn, donate_argnums=tuple(donate_argnums))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        txt = j.lower(*args).as_text()
    return {int(m.group(1)) for m in _ALIAS_RE.finditer(txt)}


def _arg_of_leaf(args: Sequence, leaf_idx: int) -> int:
    """Top-level positional argument owning flat leaf ``leaf_idx``."""
    bound = 0
    for i, a in enumerate(args):
        bound += len(jax.tree_util.tree_leaves(a))
        if leaf_idx < bound:
            return i
    return len(args) - 1


@register_pass("donation-alias", "jaxpr", (R.JAXPR_DONATED_ALIAS,),
               "donated buffers unreachable through non-donated aliases")
def lint_donation(fn, args: Sequence, donate_argnums: Sequence[int],
                  aliased_args: Dict[int, str],
                  location: str = "") -> List[LintFinding]:
    """Use-after-donate detector.

    ``aliased_args`` names the top-level arguments whose buffers are
    reachable through OTHER live references — the rid carry (aliases
    the previous beat's in-flight ``results["_join_rids"]``) and the
    staged query/update buffers (reused across pipeline slots).
    Donating any of their leaves frees a buffer something else still
    reads — the DECLARATION is the hazard (whether a given lowering
    materializes the alias is backend luck), so aliased donations are
    flagged from ``donate_argnums`` itself.  Also flags declared
    donations the lowering dropped entirely (warning: the in-place
    carry roll-forward silently degraded to a copy)."""
    out = []
    declared = set(donate_argnums)
    donated = donated_leaf_args(fn, args, donate_argnums)
    donated_top = {_arg_of_leaf(args, leaf) for leaf in donated}
    for argnum in sorted(declared & set(aliased_args)):
        out.append(LintFinding(
            R.JAXPR_DONATED_ALIAS,
            f"argument {argnum} ({aliased_args[argnum]}) is donated "
            "but reachable through a non-donated alias — "
            "use-after-donate", location=location))
    for argnum in sorted(declared - donated_top - set(aliased_args)):
        if len(jax.tree_util.tree_leaves(args[argnum])) == 0:
            continue
        out.append(LintFinding(
            R.JAXPR_DONATED_ALIAS,
            f"declared donation of argument {argnum} was dropped by "
            "the lowering (carry roll-forward degraded to a copy)",
            severity="warning", location=location))
    return out
