"""Compile-only checks: every main-path Pallas kernel compiles for a
described (not attached) TPU v5e, at TPC-W widths.

The shapes come from the real lowered plan of the index-less TPC-W
catalog at 10,000 items x 100 emulated browsers (288,000 customers, the
TPC-W clause-4 population): the scan stages' windows, the 13-word query
window, the tables' capacities and dirty sets, and the bucket width
``partition_layout`` picks from the measured key occupancy.  Nothing
runs: Mosaic and XLA's TPU compiler either accept each kernel or raise
what the chip's compiler would raise.  The topology is described inside
a fixture, so collecting this file touches no TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.backends import FusedJoinIn, FusedScanIn
from repro.core.executor import _measure_key_stats
from repro.core.lowering import lower_plan
from repro.workloads import tpcw

ITEMS, CUSTOMERS = 10000, 288000
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def lowered():
    plan = tpcw.build_tpcw_plan(ITEMS, CUSTOMERS, dense_pk_index=False)
    data = tpcw.generate_data(np.random.default_rng(0), ITEMS, CUSTOMERS)
    return lower_plan(plan, key_stats=_measure_key_stats(plan, data))


def _struct(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scan(lowered, table):
    return next(s for s in lowered.scans if s.table == table)


def _join(lowered, spine, pk_table):
    return next(j for j in lowered.joins
                if (j.spine, j.pk_table) == (spine, pk_table))


def _cap(lowered, table):
    return lowered.plan.catalog.schemas[table].capacity


def _compile(fn, *args):
    """Compile for the described chip; the kernel must be a Mosaic custom
    call (not interpreted) and the program must fit the chip's HBM."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    if mem is not None:
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("table", ["order_line", "item"])
def test_clockscan_compiles(one_chip, lowered, table):
    from repro.kernels.clockscan import clockscan_pallas
    st = _scan(lowered, table)
    C, T, Q = len(st.cols), _cap(lowered, table), st.q_window
    _compile(lambda c, lo, hi, v: clockscan_pallas(c, lo, hi, v,
                                                   interpret=False),
             _struct(one_chip, (C, T)), _struct(one_chip, (C, Q)),
             _struct(one_chip, (C, Q)), _struct(one_chip, (T,), bool))


def test_shared_groupby_compiles(one_chip, lowered):
    from repro.kernels.shared_groupby import shared_groupby_pallas
    g = lowered.groups[0]
    T, W = g.union_cap, g.whi - g.wlo
    _compile(lambda c, v, m: shared_groupby_pallas(
                 c, v, m, g.agg.n_groups, interpret=False),
             _struct(one_chip, (T,)), _struct(one_chip, (T,)),
             _struct(one_chip, (T, W), jnp.uint32))


def test_partitioned_join_compiles(one_chip, lowered):
    """order_line -> orders: the widest spine and the most buckets."""
    from repro.kernels.partitioned_join import partitioned_join_pallas
    j = _join(lowered, "order_line", "orders")
    Tl, Tr, W = _cap(lowered, "order_line"), _cap(lowered, "orders"), \
        lowered.W
    P, B = j.n_partitions, j.bucket_cap
    _compile(lambda kl, ml, bk, br, bd, mr: partitioned_join_pallas(
                 kl, ml, bk, br, bd, mr, interpret=False),
             _struct(one_chip, (Tl,)),
             _struct(one_chip, (Tl, W), jnp.uint32),
             _struct(one_chip, (P, B)), _struct(one_chip, (P, B)),
             _struct(one_chip, (P,)),
             _struct(one_chip, (Tr, W), jnp.uint32))


def test_bitmask_join_compiles(one_chip, lowered):
    """A small index-less PK side (128 rows) under a wide spine."""
    from repro.kernels.bitmask_join import bitmask_join_pallas
    Tl, Tr, W = _cap(lowered, "address"), _cap(lowered, "country"), \
        lowered.W
    _compile(lambda kl, ml, kr, mr, vr: bitmask_join_pallas(
                 kl, ml, kr, mr, vr, interpret=False),
             _struct(one_chip, (Tl,)),
             _struct(one_chip, (Tl, W), jnp.uint32),
             _struct(one_chip, (Tr,)),
             _struct(one_chip, (Tr, W), jnp.uint32),
             _struct(one_chip, (Tr,), bool))


def test_delta_scan_compiles(one_chip, lowered):
    from repro.kernels.fused_delta import delta_scan_pallas
    st = _scan(lowered, "item")
    C, T, Q = len(st.cols), _cap(lowered, "item"), st.q_window
    D = lowered.plan.catalog.schemas["item"].dirty_cap
    _compile(lambda c, lo, hi, v, r: delta_scan_pallas(
                 c, lo, hi, v, r, interpret=False),
             _struct(one_chip, (C, T)), _struct(one_chip, (C, Q)),
             _struct(one_chip, (C, Q)), _struct(one_chip, (T,), bool),
             _struct(one_chip, (D,)))


def test_delta_join_compiles(one_chip, lowered):
    from repro.kernels.fused_delta import delta_join_pallas
    j = _join(lowered, "order_line", "orders")
    Tl = _cap(lowered, "order_line")
    D = lowered.plan.catalog.schemas["order_line"].dirty_cap
    P, B = j.n_partitions, j.bucket_cap
    _compile(lambda kl, r, bk, br, bd: delta_join_pallas(
                 kl, r, bk, br, bd, interpret=False),
             _struct(one_chip, (Tl,)), _struct(one_chip, (D,)),
             _struct(one_chip, (P, B)), _struct(one_chip, (P, B)),
             _struct(one_chip, (P,)))


def test_fused_delta_compiles(one_chip, lowered):
    """The steady-state beat's one launch: every predicated stage's pane
    tiles and dirty rows plus every carried join's dirty probes."""
    from repro.kernels.fused_delta import fused_delta_pallas
    schemas = lowered.plan.catalog.schemas
    scalar = _struct(one_chip, ())
    scan_in = []
    for st in lowered.scans:
        if not st.cols:
            continue
        C, T, Q = len(st.cols), schemas[st.table].capacity, st.q_window
        A, D = st.delta_words, schemas[st.table].dirty_cap
        scan_in.append(FusedScanIn(
            cols=_struct(one_chip, (C, T)), lo=_struct(one_chip, (C, Q)),
            hi=_struct(one_chip, (C, Q)),
            lo_p=_struct(one_chip, (C, 32 * A)),
            hi_p=_struct(one_chip, (C, 32 * A)),
            valid=_struct(one_chip, (T,), bool),
            carry=_struct(one_chip, (T, Q // 32), jnp.uint32),
            w0=scalar, span=scalar, rows=_struct(one_chip, (D,)),
            dn=scalar))
    join_in = []
    for j in lowered.joins:
        if j.kind != "partitioned":
            continue
        Tl, D = schemas[j.spine].capacity, schemas[j.spine].dirty_cap
        P, B = j.n_partitions, j.bucket_cap
        join_in.append(FusedJoinIn(
            keys=_struct(one_chip, (Tl,)), rows=_struct(one_chip, (D,)),
            dn=scalar, bkeys=_struct(one_chip, (P, B)),
            brows=_struct(one_chip, (P, B)),
            bounds=_struct(one_chip, (P,)),
            rid_carry=_struct(one_chip, (Tl,))))
    assert scan_in and join_in
    _compile(lambda s, j: fused_delta_pallas(s, j, interpret=False),
             tuple(scan_in), tuple(join_in))
