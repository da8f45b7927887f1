"""Differential tests: Pallas fused kernel vs the XLA reference ops.

Runs under the Pallas interpreter on the CPU mesh (conftest forces
JAX_PLATFORMS=cpu), so the kernel logic is exercised everywhere; on TPU
the same code path compiles to a real Mosaic kernel.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from kcp_tpu.ops.diff import sync_decisions  # noqa: E402
from kcp_tpu.ops.labelmatch import fanout_match  # noqa: E402
from kcp_tpu.ops.pallas_kernels import decide_and_match  # noqa: E402


def _random_case(rng, b=256, s=64, l=8, c=16):
    up = rng.integers(1, 2**32, size=(b, s), dtype=np.uint32)
    down = up.copy()
    # dirty some rows: spec lanes (first half) and status lanes (second)
    dirty = rng.random(b) < 0.3
    down[dirty] ^= rng.integers(0, 2, size=(dirty.sum(), s), dtype=np.uint32) * 7
    upe = rng.random(b) < 0.9
    dne = rng.random(b) < 0.85
    mask = np.zeros(s, dtype=bool)
    mask[s // 2:] = True
    sel = rng.integers(1, 1000, size=c, dtype=np.uint32)
    pair = rng.integers(1, 1000, size=(b, l), dtype=np.uint32)
    return up, upe, down, dne, mask, pair, sel


class TestDecideAndMatch:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_reference_ops(self, seed):
        rng = np.random.default_rng(seed)
        up, upe, down, dne, mask, pair, sel = _random_case(rng)

        decision, upsync, counts = decide_and_match(
            up, upe, down, dne, mask, pair, sel, block_rows=64, interpret=True
        )

        ref = sync_decisions(
            jnp.asarray(up), jnp.asarray(upe), jnp.asarray(down),
            jnp.asarray(dne), jnp.asarray(mask),
        )
        np.testing.assert_array_equal(np.asarray(decision), np.asarray(ref.decision))
        np.testing.assert_array_equal(np.asarray(upsync), np.asarray(ref.status_upsync))

        match = np.asarray(fanout_match(jnp.asarray(pair), jnp.asarray(sel)))
        ref_counts = (match & upe[:, None]).sum(axis=0)
        np.testing.assert_array_equal(np.asarray(counts), ref_counts)

    def test_matches_reconcile_step_lane(self):
        """The kernel must agree with the model's actual fan-out lane."""
        from kcp_tpu.models.reconcile_model import (
            example_deltas, example_state, reconcile_step,
        )

        state = example_state(b=128, s=16, r=8, p=4, l=4, c=8, seed=5)
        deltas = example_deltas(b=128, s=16, d=16, seed=6)
        st = jax.tree.map(jnp.asarray, state)
        dl = jax.tree.map(jnp.asarray, deltas)
        _, out = reconcile_step(st, dl)
        # the kernel sees post-scatter mirrors; rebuild them host-side
        from kcp_tpu.ops.diff import apply_deltas
        upv, upe = apply_deltas(st.up_vals, st.up_exists, dl.idx,
                                dl.vals, dl.exists, dl.valid & ~dl.side)
        dnv, dne = apply_deltas(st.down_vals, st.down_exists, dl.idx,
                                dl.vals, dl.exists, dl.valid & dl.side)
        decision, upsync, counts = decide_and_match(
            np.asarray(upv), np.asarray(upe), np.asarray(dnv), np.asarray(dne),
            np.asarray(state.status_mask), np.asarray(state.pair_hashes),
            np.asarray(state.sel_hashes), block_rows=64, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(decision), np.asarray(out.decision))
        np.testing.assert_array_equal(np.asarray(upsync), np.asarray(out.status_upsync))
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(out.match_counts))

    def test_single_block_and_multi_block_agree(self):
        rng = np.random.default_rng(3)
        up, upe, down, dne, mask, pair, sel = _random_case(rng, b=128)
        one = decide_and_match(up, upe, down, dne, mask, pair, sel,
                               block_rows=128, interpret=True)
        many = decide_and_match(up, upe, down, dne, mask, pair, sel,
                                block_rows=32, interpret=True)
        for a, b in zip(one, many):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_all_decision_codes_reachable(self):
        s = 8
        up = np.full((4, s), 5, dtype=np.uint32)
        down = up.copy()
        upe = np.array([True, True, False, True])
        dne = np.array([False, True, True, True])
        down[1, 0] = 99  # spec lane differs -> UPDATE
        mask = np.zeros(s, dtype=bool)
        pair = np.zeros((4, 2), dtype=np.uint32)
        sel = np.zeros(2, dtype=np.uint32)
        decision, upsync, _ = decide_and_match(
            up, upe, down, dne, mask, pair, sel, block_rows=4, interpret=True
        )
        assert list(np.asarray(decision)) == [1, 2, 3, 0]  # CREATE/UPDATE/DELETE/NOOP
        assert not np.asarray(upsync).any()

    def test_status_lane_triggers_upsync_not_update(self):
        s = 8
        up = np.full((2, s), 5, dtype=np.uint32)
        down = up.copy()
        mask = np.zeros(s, dtype=bool)
        mask[4:] = True
        down[0, 6] = 99  # status lane only
        upe = np.array([True, True])
        dne = np.array([True, True])
        pair = np.zeros((2, 2), dtype=np.uint32)
        sel = np.zeros(2, dtype=np.uint32)
        decision, upsync, _ = decide_and_match(
            up, upe, down, dne, mask, pair, sel, block_rows=2, interpret=True
        )
        assert list(np.asarray(decision)) == [0, 0]
        assert list(np.asarray(upsync)) == [True, False]

    def test_indivisible_block_raises(self):
        rng = np.random.default_rng(4)
        up, upe, down, dne, mask, pair, sel = _random_case(rng, b=96)
        with pytest.raises(ValueError, match="not divisible"):
            decide_and_match(up, upe, down, dne, mask, pair, sel,
                             block_rows=64, interpret=True)


class TestPerRowMask:
    """The serving core's shared buckets carry [B, S] per-row masks —
    the kernel must accept them (round-4 integration)."""

    @pytest.mark.parametrize("seed", [0, 5])
    def test_per_row_mask_matches_reference(self, seed):
        rng = np.random.default_rng(seed)
        up, upe, down, dne, _mask, pair, sel = _random_case(rng)
        b, s = up.shape
        rowmask = rng.random((b, s)) < 0.4

        decision, upsync, counts = decide_and_match(
            up, upe, down, dne, rowmask, pair, sel, block_rows=64,
            interpret=True,
        )
        ref = sync_decisions(
            jnp.asarray(up), jnp.asarray(upe), jnp.asarray(down),
            jnp.asarray(dne), jnp.asarray(rowmask),
        )
        np.testing.assert_array_equal(np.asarray(decision), np.asarray(ref.decision))
        np.testing.assert_array_equal(np.asarray(upsync), np.asarray(ref.status_upsync))
        match = np.asarray(fanout_match(jnp.asarray(pair), jnp.asarray(sel)))
        np.testing.assert_array_equal(
            np.asarray(counts), (match & upe[:, None]).sum(axis=0))


class TestReconcileStepPallasLane:
    """use_pallas=True is the SERVED integration (FusedBucket passes it
    when KCP_PALLAS=1): the whole step must be bit-identical."""

    def test_step_identical_with_and_without_pallas(self):
        from kcp_tpu.models.reconcile_model import (
            example_deltas, example_state, reconcile_step,
        )

        state = example_state(b=256, s=64, r=16, p=4, l=8, c=16, dirty_frac=0.2)
        deltas = example_deltas(b=256, s=64, d=32)
        _, ref = jax.jit(reconcile_step,
                         static_argnames=("use_pallas",))(state, deltas)
        _, out = jax.jit(reconcile_step,
                         static_argnames=("use_pallas",))(
            state, deltas, use_pallas=True)
        for name in ref._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, name)), np.asarray(getattr(out, name)),
                err_msg=name)

    def test_served_core_with_pallas_end_to_end(self):
        """start_syncer with a KCP_PALLAS core: sync results identical to
        the XLA path (the serving-level differential test)."""
        import asyncio

        from kcp_tpu.client import Client
        from kcp_tpu.store import LogicalStore
        from kcp_tpu.syncer import start_syncer
        from kcp_tpu.syncer.core import FusedCore
        from kcp_tpu.syncer.engine import CLUSTER_LABEL

        def cm(name, data):
            return {"apiVersion": "v1", "kind": "ConfigMap",
                    "metadata": {"name": name, "namespace": "default",
                                 "labels": {CLUSTER_LABEL: "c1"}},
                    "data": data}

        async def eventually(pred, timeout=15.0):
            deadline = asyncio.get_event_loop().time() + timeout
            while True:
                try:
                    if pred():
                        return
                except Exception:
                    pass
                if asyncio.get_event_loop().time() > deadline:
                    raise AssertionError("condition not reached")
                await asyncio.sleep(0.01)

        async def drive(use_pallas):
            kcp, phys = LogicalStore(), LogicalStore()
            up, down = Client(kcp, "t"), Client(phys, "p")
            syncer = await start_syncer(up, down, ["configmaps"], "c1",
                                        backend="tpu")
            eng = syncer.engines[0]
            assert eng.core.use_pallas == use_pallas
            # >128 objects so B grows past the b%128 gate and the Pallas
            # path actually runs
            for i in range(150):
                up.create("configmaps", cm(f"cm-{i}", {"v": str(i)}))
            await eventually(lambda: len(down.list("configmaps")[0]) == 150)
            dump = {o["metadata"]["name"]: o["data"]
                    for o in down.list("configmaps")[0]}
            bucket = eng._section.bucket
            assert bucket.B >= 256
            assert eng.core._fleet.use_pallas == use_pallas
            await syncer.stop()
            return dump

        async def scenario(use_pallas):
            # bind a pre-made core to this loop so for_current_loop
            # returns it (env-independent constructor arg)
            core = FusedCore(use_pallas=use_pallas)
            core._loop = asyncio.get_running_loop()
            FusedCore._instances[id(core._loop)] = core
            return await drive(use_pallas)

        with_pallas = asyncio.run(scenario(True))
        without = asyncio.run(scenario(False))
        assert with_pallas == without


class TestShardedPallas:
    """decide_and_match on a mesh: shard_map runs the kernel per device
    on its local row block; counts psum across the row axes. Must match
    the unsharded reference exactly (round-4 mesh+pallas composition)."""

    @pytest.mark.parametrize("spec", ["4x2", "8", "2x2x2"])
    def test_sharded_kernel_matches_reference(self, spec):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from kcp_tpu.ops.pallas_kernels import decide_and_match_sharded
        from kcp_tpu.parallel.mesh import (
            HOSTS_AXIS, SLOTS_AXIS, TENANTS_AXIS, mesh_from_spec,
        )

        mesh = mesh_from_spec(spec)
        rng = np.random.default_rng(11)
        up, upe, down, dne, _m, pair, sel = _random_case(rng, b=256)
        rowmask = rng.random((256, 64)) < 0.4

        row = (HOSTS_AXIS, TENANTS_AXIS) if HOSTS_AXIS in mesh.axis_names \
            else TENANTS_AXIS
        dev = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
        dec, ups, counts = decide_and_match_sharded(
            mesh,
            dev(up, P(row, SLOTS_AXIS)), dev(upe, P(row)),
            dev(down, P(row, SLOTS_AXIS)), dev(dne, P(row)),
            dev(rowmask, P(row, SLOTS_AXIS)), dev(pair, P(row, None)),
            dev(sel, P()), interpret=True,
        )
        ref = sync_decisions(
            jnp.asarray(up), jnp.asarray(upe), jnp.asarray(down),
            jnp.asarray(dne), jnp.asarray(rowmask))
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(ref.decision))
        np.testing.assert_array_equal(np.asarray(ups),
                                      np.asarray(ref.status_upsync))
        match = np.asarray(fanout_match(jnp.asarray(pair), jnp.asarray(sel)))
        np.testing.assert_array_equal(
            np.asarray(counts), (match & upe[:, None]).sum(axis=0))

    def test_step_with_mesh_and_pallas_matches_plain(self):
        """The whole fused step: sharded + Pallas == unsharded XLA."""
        from kcp_tpu.models.reconcile_model import (
            example_deltas, example_state, reconcile_step,
        )
        from kcp_tpu.parallel.mesh import make_mesh, shard_state

        mesh = make_mesh(n_devices=8, tenants=8, slots=1)
        # local rows = 1024/8 = 128 -> the pallas gate passes per shard
        state = example_state(b=1024, s=64, r=16, p=8, l=8, c=16,
                              dirty_frac=0.2)
        deltas = example_deltas(b=1024, s=64, d=64)
        _, ref = jax.jit(reconcile_step,
                         static_argnames=("use_pallas", "mesh"))(state, deltas)

        sstate = shard_state(state, mesh)
        _, out = jax.jit(reconcile_step,
                         static_argnames=("use_pallas", "mesh"))(
            sstate, deltas, use_pallas=True, mesh=mesh)
        for name in ref._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, name)), np.asarray(getattr(out, name)),
                err_msg=name)

    def test_serving_core_with_mesh_and_pallas(self):
        """start_syncer with BOTH a mesh and KCP_PALLAS: results match
        the plain path (small buckets fall back to XLA via the
        local-row gate — correctness either way)."""
        import asyncio

        from kcp_tpu.client import Client
        from kcp_tpu.parallel.mesh import make_mesh
        from kcp_tpu.store import LogicalStore
        from kcp_tpu.syncer import start_syncer
        from kcp_tpu.syncer.core import FusedCore
        from kcp_tpu.syncer.engine import CLUSTER_LABEL

        mesh = make_mesh(n_devices=8, tenants=8, slots=1)

        async def main():
            core = FusedCore(mesh=mesh, use_pallas=True)
            core._loop = asyncio.get_running_loop()
            FusedCore._instances[id(core._loop)] = core
            kcp, phys = LogicalStore(), LogicalStore()
            up, down = Client(kcp, "t"), Client(phys, "p")
            syncer = await start_syncer(up, down, ["configmaps"], "c1",
                                        backend="tpu")
            for i in range(40):
                up.create("configmaps", {
                    "apiVersion": "v1", "kind": "ConfigMap",
                    "metadata": {"name": f"cm-{i}", "namespace": "default",
                                 "labels": {CLUSTER_LABEL: "c1"}},
                    "data": {"v": str(i)}})
            deadline = asyncio.get_event_loop().time() + 15
            while len(down.list("configmaps")[0]) != 40:
                if asyncio.get_event_loop().time() > deadline:
                    raise AssertionError("sync did not converge")
                await asyncio.sleep(0.02)
            assert syncer.engines[0].core.use_pallas
            assert syncer.engines[0]._section.bucket.mesh is mesh
            await syncer.stop()

        asyncio.run(main())

    def test_non_divisible_b_falls_back_instead_of_crashing(self):
        """B=1028 over an 8-way mesh: local rows are fractional — the
        gate must route to the XLA lanes, not crash in shard_map."""
        from kcp_tpu.models.reconcile_model import (
            example_deltas, example_state, reconcile_step,
        )
        from kcp_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(n_devices=8, tenants=8, slots=1)
        state = example_state(b=1028, s=16, r=8, p=4, l=2, c=4)
        deltas = example_deltas(b=1028, s=16, d=16)
        _, out = jax.jit(reconcile_step,
                         static_argnames=("use_pallas", "mesh"))(
            state, deltas, use_pallas=True, mesh=mesh)
        _, ref = jax.jit(reconcile_step,
                         static_argnames=("use_pallas", "mesh"))(state, deltas)
        np.testing.assert_array_equal(np.asarray(out.decision),
                                      np.asarray(ref.decision))


def test_max_block_rows_vmem_cap():
    """The block selector honors the measured scoped-VMEM budget: wider
    buckets get smaller blocks, and a bucket too wide for even a 128-row
    block falls back to the XLA lanes (0)."""
    from kcp_tpu.ops.pallas_kernels import max_block_rows

    assert max_block_rows(131072, 64, labels=8) == 2048
    assert max_block_rows(131072, 128, labels=8) == 1024
    assert max_block_rows(131072, 1024) == 128
    assert max_block_rows(131072, 2048) == 0  # over budget at any block
    # wide label capacity eats the same budget (review finding: L rides
    # in the block too)
    assert max_block_rows(131072, 64, labels=512) == 512
    # the bucket-wide [S] mask form loads one fewer slots column
    assert max_block_rows(131072, 1536, per_row_mask=False) == 128
    # divisibility: block must divide the local rows
    assert max_block_rows(1024 + 128, 64) == 128
    assert max_block_rows(100, 64) == 0  # not 128-divisible


@pytest.mark.parametrize("b,s", [(100, 16), (128, 4096)],
                         ids=["rows-not-128-multiple", "over-vmem-budget"])
def test_gate_that_serves_the_xla_lanes_says_so(b, s, caplog):
    """use_pallas=True on a shape the kernel's gate refuses still serves
    (the XLA lanes), but never silently: one warning per traced shape."""
    import logging

    from kcp_tpu.models.reconcile_model import (
        example_deltas,
        example_state,
        reconcile_step,
    )

    st = example_state(b=b, s=s, r=8, p=8, l=2, c=4, seed=1)
    dl = example_deltas(b=b, s=s, d=8, seed=2)
    step = jax.jit(reconcile_step, static_argnames=("use_pallas",))
    with caplog.at_level(logging.WARNING, "kcp_tpu.models.reconcile_model"):
        _state, plain = step(st, dl, use_pallas=False)
        assert not caplog.records
        _state, gated = step(st, dl, use_pallas=True)
        step(st, dl, use_pallas=True)  # cached trace: no second warning
    said = [r for r in caplog.records if "serves the XLA lanes" in r.message]
    assert len(said) == 1, caplog.text
    np.testing.assert_array_equal(np.asarray(plain.decision),
                                  np.asarray(gated.decision))
