"""Compile the main path's device programs for a *described* TPU v5e.

No chip is attached and nothing runs: the TPU compiler installed beside
jax compiles for a ``v5e:2x2`` topology that is only described, and raises
what the chip's compiler would raise — a misaligned slice, a kernel over
its fast-memory budget, a step that does not fit 16 GB, a kernel that
cannot be partitioned. ``chip_smoke.py`` runs the same programs at the
same widths on the real device.

Everything built from the topology lives in module-scoped fixtures that
skip when it cannot be described: only one process may hold the TPU
library, so the call must never happen at import or collection. The
persistent compile cache is off around these tests (such a compile can
be written to it but not read back without a chip).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from kcp_tpu.models.reconcile_model import (
    ReconcileState,
    ack_lane_rows,
    reconcile_step_fleet,
    reconcile_step_packed,
)
from kcp_tpu.ops import pallas_kernels
from kcp_tpu.ops.pallas_kernels import decide_and_match

# the widths chip_smoke.py runs on the chip
B, S, L, C = 131072, 64, 8, 64
R, PC, D, K = 16384, 8, 1024, 8192
ACKS, SEGS = 8192, 8
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from kcp_tpu.parallel.mesh import SLOTS_AXIS, TENANTS_AXIS

    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices).reshape(4, 1), (TENANTS_AXIS, SLOTS_AXIS))


def _state(row, flags, placement, placement_rows, labels, selectors):
    """ReconcileState of ShapeDtypeStructs at the chip_smoke widths, in
    the served layout (per-row status masks)."""
    def sds(shape, dtype, sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    return ReconcileState(
        up_vals=sds((B, S), jnp.uint32, row),
        up_exists=sds((B,), jnp.bool_, flags),
        down_vals=sds((B, S), jnp.uint32, row),
        down_exists=sds((B,), jnp.bool_, flags),
        status_mask=sds((B, S), jnp.bool_, row),
        replicas=sds((R,), jnp.int32, placement_rows),
        avail=sds((R, PC), jnp.bool_, placement),
        current=sds((R, PC), jnp.int32, placement),
        pair_hashes=sds((B, L), jnp.uint32, labels),
        sel_hashes=sds((C,), jnp.uint32, selectors),
    )


@pytest.fixture(scope="module")
def chip_state(one_chip):
    return _state(*[one_chip] * 6)


@pytest.fixture(scope="module")
def chip_wire(one_chip):
    return (jax.ShapeDtypeStruct((D, S + 2), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct((ACKS,), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("per_row_mask", [False, True],
                         ids=["bucket-mask", "row-mask"])
def test_decide_and_match_compiles_as_a_kernel(chip_state, per_row_mask):
    st = chip_state
    mask = (st.status_mask if per_row_mask else jax.ShapeDtypeStruct(
        (S,), jnp.bool_, sharding=st.up_exists.sharding))
    lowered = decide_and_match.lower(
        st.up_vals, st.up_exists, st.down_vals, st.down_exists, mask,
        st.pair_hashes, st.sel_hashes, block_rows=2048, interpret=False)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()  # Mosaic: tiling, alignment, scoped-VMEM budget


def test_donated_packed_step_fits_one_v5e(chip_state, chip_wire):
    packed, acks = chip_wire
    step = jax.jit(reconcile_step_packed, donate_argnums=(0,),
                   static_argnames=("patch_capacity", "use_pallas", "mesh"))
    compiled = step.lower(chip_state, packed, acks, patch_capacity=K).compile()
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < need < V5E_HBM_BYTES, mem
    # the resident state is donated: its buffers alias the outputs
    assert mem.alias_size_in_bytes >= 2 * B * S * 4, mem


def _fleet_step():
    """The serving default, jitted as syncer/core.py FleetBatch jits it."""
    return jax.jit(reconcile_step_fleet, donate_argnums=(0, 1),
                   static_argnames=("ack_capacity", "patch_capacity",
                                    "seg_capacity", "use_pallas", "mesh"))


def _fleet_wire(sharding):
    """The one array a fleet tick puts: D event rows, the ack lane in its
    tail rows (models/reconcile_model.py WireBuffers)."""
    return jax.ShapeDtypeStruct((D + ack_lane_rows(ACKS, S + 2), S + 2),
                                jnp.uint32, sharding=sharding)


def test_fleet_step_compiles_for_one_chip(chip_state, one_chip):
    seg = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = _fleet_step().lower(
        chip_state, seg, _fleet_wire(one_chip), ack_capacity=ACKS,
        patch_capacity=K, seg_capacity=SEGS).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
    assert "all-reduce" not in compiled.as_text()


def test_fleet_step_shards_over_four_chips(mesh4):
    from kcp_tpu.parallel.mesh import state_shardings

    sh = state_shardings(mesh4)
    st = _state(sh["rows"], sh["flags"], sh["placement"],
                sh["placement_rows"], sh["labels"], sh["selectors"])
    repl = NamedSharding(mesh4, P())
    seg = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sh["flags"])
    compiled = _fleet_step().lower(
        st, seg, _fleet_wire(repl), ack_capacity=ACKS, patch_capacity=K,
        seg_capacity=SEGS, mesh=mesh4).compile()
    # the stats and per-segment counters reduce across the row shards
    assert "all-reduce" in compiled.as_text()
    mem = compiled.memory_analysis()
    single = 2 * B * S * 4  # the two value mirrors alone, unsharded
    assert mem.argument_size_in_bytes < single, (
        "per-device arguments as large as the whole state: not sharded", mem)
    # the resident state comes back laid out as it went in (donation)
    out_state = compiled.output_shardings[0]
    assert out_state.up_vals.is_equivalent_to(sh["rows"], 2)


def test_pallas_step_lowers_to_a_tpu_kernel(chip_state, chip_wire, monkeypatch):
    # the gate asks jax.default_backend(), which is the CPU here: steer it
    # in the test, as a chip would answer
    monkeypatch.setattr(pallas_kernels, "default_interpret", lambda: False)
    packed, acks = chip_wire
    step = jax.jit(functools.partial(reconcile_step_packed, use_pallas=True),
                   static_argnames=("patch_capacity",))
    lowered = step.lower(chip_state, packed, acks, patch_capacity=K)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
