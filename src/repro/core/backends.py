"""Operator backend registry: jnp reference ops vs Pallas TPU kernels.

The lowered operator graph (lowering.py) is backend-agnostic: every stage
that has a compute hot-spot resolves its implementation through this
registry at plan-build time.  Two backends ship:

  * ``jnp``    — the pure-jnp reference operators (kernels/ref.py).  This
                 is the CPU execution path AND the semantic oracle every
                 Pallas kernel is validated against.
  * ``pallas`` — the TPU kernels (kernels/clockscan.py, bitmask_join.py,
                 shared_groupby.py), run in interpret mode off-TPU so the
                 full engine path stays testable on CPU.

Backend surface (the shared-operator hot loops):

  scan(cols, lo, hi, valid)                 -> uint32[T, W]   (ClockScan)
  scan_delta(cols, lo, hi, valid, rows)     -> uint32[D, W]   (dirty rows)
  join_block(kl, ml, kr, mr, valid_r)       -> (rid, mask)    (shared join)
  join_partitioned(kl, ml, bkeys, brows,
                   bounds, mr)              -> (rid, mask)    (bucketed join)
  join_delta(kl, rows, bkeys, brows,
             bounds)                        -> rid int32[D]   (dirty probe)
  groupby(codes, vals, mask, n_groups)      -> (count, sum)
  fused_delta(scan_in, join_in)             -> (words, rids)  (OPTIONAL —
      the whole delta beat in ONE op: every predicated stage's admission
      pane + dirty-row rescan merged into its carried words, every
      carried join's dirty-spine-row probe merged into its carried rid
      array.  ``scan_in``/``join_in`` are tuples of FusedScanIn /
      FusedJoinIn below.  A backend that leaves this None falls back to
      the chained scan/scan_delta/join_delta ops in build_delta_cycle.)

Everything else in the cycle — the dense PK-index gather join, union
compression, argsort and result routing — lowers directly to XLA
gather/sort/scatter and is shared verbatim by both backends (see
core/operators.py).

Resolution: ``resolve_backend("jnp"|"pallas"|"auto")``.  ``auto`` honours
the ``REPRO_KERNELS`` environment override (the kernel test-suite's knob;
``ref`` is accepted as an alias of ``jnp``), else picks Pallas exactly
when a TPU backend is present.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax


class FusedScanIn(NamedTuple):
    """One predicated scan stage's inputs to the fused delta op.

    The lowering computes the pane window host-free (``_pane_window``)
    and pre-slices the pane predicate matrix, so the op itself never
    re-derives admission state.  ``rows`` is the stage table's sorted
    distinct dirty-row set padded with the capacity sentinel (==
    ``cols.shape[1]``), ``dn`` its live count — the op may use ``dn``
    (and ``span``) to skip no-op phases, which is exact because a
    zero-span pane recompute and an all-sentinel scatter are both
    identities on the carried words.
    """
    cols: object          # int32[C, T] predicated columns
    lo: object            # int32[C, Q] full-window predicate lows
    hi: object            # int32[C, Q] full-window predicate highs
    lo_p: object          # int32[C, 32*A] pane slice of lo at w0
    hi_p: object          # int32[C, 32*A] pane slice of hi at w0
    valid: object         # bool[T]
    carry: object         # uint32[T, w] previous heartbeat's words
    w0: object            # int32 scalar: pane's first word column
    span: object          # int32 scalar: changed-word span (0 = none)
    rows: object          # int32[D] dirty rows (sentinel == T pads)
    dn: object            # int32 scalar: live dirty-row count


class FusedJoinIn(NamedTuple):
    """One carried (non-gather) join's inputs to the fused delta op.

    Block-kind joins arrive as single-bucket pseudo-partitions (the
    whole PK side is one pane with bound INT_MIN), so every carried join
    probes through the same one-bucket-per-dirty-row path.
    """
    keys: object          # int32[Tl] the spine's full fk column
    rows: object          # int32[D] dirty spine rows (sentinel == Tl)
    dn: object            # int32 scalar: live dirty-row count
    bkeys: object         # int32[P, B] bucket keys
    brows: object         # int32[P, B] bucket row ids (-1 pad)
    bounds: object        # int32[P] bucket lower bounds
    rid_carry: object     # int32[Tl] previous heartbeat's rids


@dataclasses.dataclass(frozen=True)
class OperatorBackend:
    """Implementations for the shared-operator hot loops.

    All callables must be traceable (pure jax) — they are baked into the
    always-on compiled plan at build time.
    """
    name: str
    scan: Callable        # (cols[C,T], lo[C,Q], hi[C,Q], valid[T]) -> u32[T,W]
    join_block: Callable  # (kl[Tl], ml[Tl,W], kr[Tr], mr[Tr,W], vr[Tr])
                          #   -> (rid int32[Tl], mask u32[Tl,W])
    join_partitioned: Callable  # (kl[Tl], ml[Tl,W], bkeys[P,B], brows[P,B],
                                #  bounds[P], mr[Tr,W]) -> (rid, mask)
    groupby: Callable     # (codes[T], vals[T], mask[T,W], G) -> (cnt, sum)
    scan_delta: Callable  # (cols[C,T], lo[C,Q], hi[C,Q], valid[T],
                          #  rows[D] (-1 pad)) -> u32[D,W]  (dirty rescan)
    join_delta: Callable  # (kl[Tl], rows[D] (pad >= Tl), bkeys[P,B],
                          #  brows[P,B], bounds[P]) -> rid int32[D]
                          #  (dirty-spine-row partitioned probe)
    # the whole delta beat in ONE op (None -> chained fallback):
    # (scan_in: tuple[FusedScanIn], join_in: tuple[FusedJoinIn])
    #   -> (tuple of merged uint32[T, w] words — one per scan_in entry,
    #       tuple of merged int32[Tl] rids — one per join_in entry)
    fused_delta: Optional[Callable] = None
    # (P) -> "count" | "search": the bucket routing a join_partitioned
    # launch over P buckets takes (None -> not counted)
    join_route: Optional[Callable] = None


_REGISTRY: Dict[str, OperatorBackend] = {}


def register_backend(backend: OperatorBackend) -> None:
    _REGISTRY[backend.name] = backend


def available_backends() -> Tuple[str, ...]:
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def _ensure_registered() -> None:
    if "pallas" not in _REGISTRY:
        import repro.kernels  # noqa: F401  (registers the pallas backend)


def get_backend(name: str) -> OperatorBackend:
    _ensure_registered()
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; "
                       f"available: {available_backends()}")
    return _REGISTRY[name]


def resolve_backend(kernels: str = "auto") -> OperatorBackend:
    """Map a ``kernels=`` spec to a concrete backend.

    "jnp" / "ref" -> the reference backend; "pallas" -> the TPU kernels;
    "auto" -> REPRO_KERNELS override if set, else Pallas iff running on a
    TPU backend.  Any other explicitly REGISTERED backend name resolves
    too (instrumented/wrapped backends in tests); unknown names raise
    ValueError.
    """
    if kernels in ("jnp", "ref"):
        return get_backend("jnp")
    if kernels == "pallas":
        return get_backend("pallas")
    if kernels != "auto":
        _ensure_registered()
        if kernels in _REGISTRY:
            return _REGISTRY[kernels]
        raise ValueError(f"kernels must be 'jnp', 'pallas', 'auto' or a "
                         f"registered backend name, got {kernels!r}")
    forced = os.environ.get("REPRO_KERNELS")
    if forced and forced != "auto":
        try:
            return resolve_backend(forced)
        except ValueError as e:
            raise ValueError(f"REPRO_KERNELS: {e}") from None
    return get_backend(
        "pallas" if jax.default_backend() == "tpu" else "jnp")


# ---------------------------------------------------------------------------
# The jnp reference backend (oracle + CPU execution path)
# ---------------------------------------------------------------------------


def _jnp_scan(cols, lo, hi, valid):
    from repro.kernels import ref
    return ref.clockscan_ref(cols, lo, hi, valid)


def _jnp_join_block(keys_l, mask_l, keys_r, mask_r, valid_r):
    from repro.kernels import ref
    return ref.bitmask_join_ref(keys_l, mask_l, keys_r, mask_r, valid_r)


def _jnp_join_partitioned(keys_l, mask_l, bucket_keys, bucket_rows, bounds,
                          mask_r):
    from repro.kernels import ref
    return ref.partitioned_join_ref(keys_l, mask_l, bucket_keys,
                                    bucket_rows, bounds, mask_r)


def _jnp_groupby(group_code, values, mask, n_groups):
    from repro.kernels import ref
    return ref.shared_groupby_ref(group_code, values, mask, n_groups)


def _jnp_scan_delta(cols, lo, hi, valid, rows):
    from repro.kernels import ref
    return ref.delta_scan_ref(cols, lo, hi, valid, rows)


def _jnp_join_delta(keys_l, rows, bucket_keys, bucket_rows, bounds):
    from repro.kernels import ref
    return ref.delta_join_ref(keys_l, rows, bucket_keys, bucket_rows,
                              bounds)


def _jnp_fused_delta(scan_in, join_in):
    from repro.kernels import ref
    return ref.fused_delta_ref(scan_in, join_in)


register_backend(OperatorBackend(
    name="jnp", scan=_jnp_scan, join_block=_jnp_join_block,
    join_partitioned=_jnp_join_partitioned, groupby=_jnp_groupby,
    scan_delta=_jnp_scan_delta, join_delta=_jnp_join_delta,
    fused_delta=_jnp_fused_delta, join_route=lambda P: "search"))


# ---------------------------------------------------------------------------
# Instrumentation: per-op launch counting
# ---------------------------------------------------------------------------

_COUNTED_OPS = ("scan", "join_block", "join_partitioned", "groupby",
                "scan_delta", "join_delta", "fused_delta")


def counting_backend(base: OperatorBackend, counts: Dict[str, int],
                     name: Optional[str] = None) -> OperatorBackend:
    """Wrap every op of ``base`` to bump ``counts[op]`` per invocation.
    A ``join_partitioned`` launch also bumps ``join_route_<route>``, the
    bucket routing ``base.join_route`` names for its bucket count.

    Backend ops are invoked at TRACE time (the cycles are jitted), so
    with a jitted engine the counts are the per-beat STATIC launch
    counts of the traced cycle — the executor clears the dict at traced-
    function entry, so retraces never double-count.  With ``jit=False``
    the same wrapper counts actual per-call invocations.  The wrapped
    ops delegate verbatim, so stacking this over a recording backend
    keeps the recording intact.
    """
    def wrap(op, opname):
        if op is None:
            return None

        def counted(*args, **kwargs):
            counts[opname] = counts.get(opname, 0) + 1
            if opname == "join_partitioned" and base.join_route:
                key = "join_route_" + base.join_route(args[2].shape[0])
                counts[key] = counts.get(key, 0) + 1
            return op(*args, **kwargs)
        return counted

    return OperatorBackend(
        name=name or f"counting-{base.name}", join_route=base.join_route,
        **{op: wrap(getattr(base, op), op) for op in _COUNTED_OPS})
