"""Pallas TPU kernels for SharedDB's compute hot-spots + the LM serving path.

Layout per kernel: <name>.py holds the pl.pallas_call + BlockSpec tiling;
ref.py holds the pure-jnp oracles.

Importing this package registers the ``pallas`` operator backend with
repro.core.backends, which is how the lowered global plan selects the
kernels (``SharedDBEngine(..., kernels="pallas")`` or ``"auto"`` on TPU).
The kernel modules themselves are imported lazily, at first call.  On a
TPU backend the kernels compile through Mosaic; on any other backend
(the CPU test suite) they run in the Pallas interpreter.
"""
from __future__ import annotations

import jax

from repro.core import backends as _backends


def interpret_mode() -> bool:
    """True iff the ``pallas`` backend runs its kernels in the Pallas
    interpreter — exactly when JAX's default backend is not a TPU."""
    return jax.default_backend() != "tpu"


def _pallas_scan(cols, lo, hi, valid):
    from repro.kernels.clockscan import clockscan_pallas
    return clockscan_pallas(cols, lo, hi, valid, interpret=interpret_mode())


def _pallas_join_block(keys_l, mask_l, keys_r, mask_r, valid_r):
    from repro.kernels.bitmask_join import bitmask_join_pallas
    return bitmask_join_pallas(keys_l, mask_l, keys_r, mask_r, valid_r,
                               interpret=interpret_mode())


def _pallas_join_partitioned(keys_l, mask_l, bucket_keys, bucket_rows,
                             bounds, mask_r):
    from repro.kernels.partitioned_join import partitioned_join_pallas
    return partitioned_join_pallas(keys_l, mask_l, bucket_keys, bucket_rows,
                                   bounds, mask_r,
                                   interpret=interpret_mode())


def _pallas_groupby(group_code, values, mask, n_groups: int):
    from repro.kernels.shared_groupby import shared_groupby_pallas
    return shared_groupby_pallas(group_code, values, mask, n_groups,
                                 interpret=interpret_mode())


def _pallas_scan_delta(cols, lo, hi, valid, rows):
    from repro.kernels.fused_delta import delta_scan_pallas
    return delta_scan_pallas(cols, lo, hi, valid, rows,
                             interpret=interpret_mode())


def _pallas_join_delta(keys_l, rows, bucket_keys, bucket_rows, bounds):
    from repro.kernels.fused_delta import delta_join_pallas
    return delta_join_pallas(keys_l, rows, bucket_keys, bucket_rows,
                             bounds, interpret=interpret_mode())


def _pallas_fused_delta(scan_in, join_in):
    from repro.kernels.fused_delta import fused_delta_pallas
    return fused_delta_pallas(scan_in, join_in, interpret=interpret_mode())


_backends.register_backend(_backends.OperatorBackend(
    name="pallas", scan=_pallas_scan, join_block=_pallas_join_block,
    join_partitioned=_pallas_join_partitioned, groupby=_pallas_groupby,
    scan_delta=_pallas_scan_delta, join_delta=_pallas_join_delta,
    fused_delta=_pallas_fused_delta))
