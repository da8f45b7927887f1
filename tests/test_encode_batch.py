"""The tick's encode gathers per section and stages per BUCKET
(``FusedCore._encode_sections``): however many sections bring the
tick's rows, a bucket pays for one ``stage_many`` a side.

The plain reference is the phase as it was, one staging per touched
SECTION, kept here (``per_section_reference``). A seeded fuzz of ticks
runs through both over the same scripted owners; after every tick the
host mirrors, the set of staged entries, the wire the step hands back
and every owner's patch stream must be equal. The edge cases of a
bucket-wide tick follow, one test each.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from kcp_tpu.ops.diff import DECISION_UPDATE
from kcp_tpu.ops.encode import BucketEncoder, BucketOverflow
from kcp_tpu.parallel.mesh import make_mesh
from kcp_tpu.syncer.core import MIN_ROWS, FusedCore
from test_row_reuse import Owner, both, counter, vals


def per_section_reference(section, keymasks: dict) -> None:
    """``FusedCore._encode_section`` as it stood before the bucket-wide
    path: every touched section builds its own arrays and makes its own
    one or two ``stage_many`` calls."""
    bucket = section.bucket
    keys = list(keymasks)
    masks = np.fromiter(
        (keymasks[k] | (0 if k in section.rows else 3) for k in keys),
        np.uint8, len(keys))
    absent: list = []
    try:
        ups, upes, downs, downes = [], [], [], []
        for key in keys:
            u, ue, dv, de = section.owner.fused_encode(key)
            ups.append(u)
            upes.append(ue)
            downs.append(dv)
            downes.append(de)
            if not (ue or de):
                absent.append(key)
        try:
            up_v, down_v = np.stack(ups), np.stack(downs)
        except ValueError:
            for key, u, ue, dv, de in zip(keys, ups, upes, downs, downes):
                row = section.row_for(key)
                bucket.stage(row, False, u, ue)
                bucket.stage(row, True, dv, de)
            section.refresh_mask()
            if absent:
                section.retire_gone(absent)
            return
        up_e = np.asarray(upes, bool)
        down_e = np.asarray(downes, bool)
    except BucketOverflow:
        section.owner.fused_overflow()
        return
    if absent and not all(k in section.rows for k in absent):
        ghosts = {k for k in absent if k not in section.rows}
        keep = np.fromiter((k not in ghosts for k in keys), bool, len(keys))
        keys = [k for k in keys if k not in ghosts]
        if not keys:
            return
        absent = [k for k in absent if k not in ghosts]
        masks = masks[keep]
        up_v, up_e = up_v[keep], up_e[keep]
        down_v, down_e = down_v[keep], down_e[keep]
    rows = np.fromiter((section.row_for(k) for k in keys), np.int64, len(keys))
    up_sel = (masks & 1) != 0
    if up_sel.any():
        bucket.stage_many(rows[up_sel], False, up_v[up_sel], up_e[up_sel])
    down_sel = (masks & 2) != 0
    if down_sel.any():
        bucket.stage_many(rows[down_sel], True, down_v[down_sel],
                          down_e[down_sel])
    section.refresh_mask()
    if absent:
        section.retire_gone(absent)


class PerSectionCore(FusedCore):
    def _encode_sections(self, touched: dict) -> None:
        for section, keymasks in touched.items():
            per_section_reference(section, keymasks)


# ---------------------------------------------------------- the fuzz

OWNERS = 72
RESIDENTS = 3
TICKS = 5


def widths(layout: str) -> list[int]:
    """Every owner's S: one bucket, or two of different widths."""
    if layout == "one-bucket":
        return [16] * OWNERS
    return [16 if i % 3 else 32 for i in range(OWNERS)]


def mutate(rng, owner: Owner, key, events: str) -> int:
    """Change what ``owner`` holds of ``key``; the side mask its events
    would carry."""
    s = owner.S
    up, down = owner.objs[key]
    roll = rng.random()
    if roll < 0.12:
        # gone on both sides: retired if both events are in the tick,
        # else the key keeps its row for the event still to come
        del owner.objs[key]
        return 3 if rng.random() < 0.7 else int(rng.integers(1, 3))
    if events == "one-side":
        if rng.random() < 0.5:            # a spec write
            owner.objs[key] = (vals(s, int(rng.integers(1 << 30))), down)
            return 1
        # the location answers: equal to upstream (the ack lane), or a
        # status of its own
        down = up.copy()
        if rng.random() < 0.5:
            down[-1] ^= np.uint32(rng.integers(1, 1 << 16))
        owner.objs[key] = (up, down)
        return 2
    old_up, up = up, vals(s, int(rng.integers(1 << 30)))
    roll = rng.random()
    if roll < 0.3:      # the location still echoes the spec before
        down = old_up.copy()
    else:
        down = up.copy()
        if roll < 0.65:
            down[int(rng.integers(s))] ^= np.uint32(1)
    owner.objs[key] = (up, down)
    return 3


def script(rng, owners: list[Owner], shape: str, events: str,
           tick: int) -> dict:
    """One tick's touched sections: ``{section: {key: side mask}}``, and
    the owners' objects changed to match."""
    if shape == "one":
        picked = {int(rng.integers(OWNERS)): 40}
    elif shape == "many":
        picked = dict.fromkeys(
            rng.choice(OWNERS, 57, replace=False).tolist(), 1)
    else:
        picked = dict.fromkeys(
            rng.choice(OWNERS, 30, replace=False).tolist(), 1)
        for i in list(picked)[:4]:
            picked[i] = 12
    touched: dict = {}
    for i, n in picked.items():
        owner, km = owners[i], {}
        if events == "new":
            for j in range(n):
                key = ("n", tick, i, j)
                up = vals(owner.S, int(rng.integers(1 << 30)))
                owner.objs[key] = (up, None if rng.random() < 0.5 else up.copy())
                km[key] = int(rng.integers(1, 4))
            if rng.random() < 0.2:        # a replayed DELETED: no row
                km[("ghost", tick, i)] = 1
        live = [k for k in owner.objs if k not in km]
        want = n if events != "new" else n // 4 or int(rng.random() < 0.3)
        for k in [live[int(x)] for x in
                  rng.choice(len(live), min(want, len(live)), replace=False)]:
            km[k] = mutate(rng, owner, k, events)
        if km:
            touched[owner.section] = km
    return touched


def staged_set(bucket) -> set:
    n = bucket._staged_n
    return {(int(r), int(f), bool(a), v.tobytes())
            for r, f, a, v in zip(bucket._staged_rows[:n],
                                  bucket._staged_flags[:n],
                                  bucket._staged_ack[:n],
                                  bucket._staged_vals[:n])}


def run(core_cls, mesh, shape: str, events: str, layout: str) -> list:
    """The seeded run on one core; what each tick left behind."""
    rng = np.random.default_rng([3, len(shape), len(events), len(layout)])
    core = core_cls(pipeline="serial", mesh=mesh)
    fleet = core._fleet
    owners = [Owner(core, s) for s in widths(layout)]
    for i, o in enumerate(owners):
        for j in range(RESIDENTS if shape != "one" else 44):
            o.objs[("res", j)] = both(o.S, 1000 * i + j,
                                      differ=None if j else 1)
    ticks = [{o.section: dict.fromkeys(o.objs, 3) for o in owners}]
    out = []
    for t in range(TICKS + 1):
        touched = ticks[0] if t == 0 else script(rng, owners, shape, events, t)
        core._encode_sections(touched)
        snap = {"staged": [], "mirrors": []}
        for s, b in sorted(core.buckets.items()):
            snap["staged"].append((s, staged_set(b),
                                   sorted((r, m.tobytes())
                                          for r, m in b._staged_masks.items())))
            snap["mirrors"].append(tuple(
                a.copy() for a in (b.up_vals, b.down_vals, b.up_exists,
                                   b.down_exists, b.status_mask)))
        wire, meta = fleet.submit()
        host_wire = np.asarray(wire)
        fleet.dispatch(host_wire, meta)
        snap["wire"] = host_wire
        snap["retired"] = [(b.S, sorted(rows)) for b, rows in meta.rows_retired]
        snap["streams"] = [list(o.stream) for o in owners]
        snap["rows"] = [dict(o.section.rows) for o in owners]
        snap["free"] = [list(b._free) for _s, b in sorted(core.buckets.items())]
        out.append(snap)
    assert any(s["retired"] for s in out), "the fuzz retired nothing"
    return out


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(n_devices=4, tenants=4, slots=1)


@pytest.mark.parametrize("devices", ["1-device", "4x1-mesh"])
@pytest.mark.parametrize("layout", ["one-bucket", "two-buckets"])
@pytest.mark.parametrize("events", ["new", "one-side", "both"])
@pytest.mark.parametrize("shape", ["one", "many", "mixed"])
def test_bucket_wide_staging_equals_per_section_staging(shape, events, layout,
                                                        devices, request):
    mesh = request.getfixturevalue("mesh4") if devices == "4x1-mesh" else None
    stages0 = counter("fused_stage_batches_total")
    sections0 = counter("fused_encoded_sections_total")
    got = run(FusedCore, mesh, shape, events, layout)
    stages = counter("fused_stage_batches_total") - stages0
    sections = counter("fused_encoded_sections_total") - sections0
    want = run(PerSectionCore, mesh, shape, events, layout)
    assert counter("fused_stage_batches_total") == stages0 + stages
    for t, (g, w) in enumerate(zip(got, want)):
        assert g["rows"] == w["rows"] and g["free"] == w["free"], t
        for (gs, gset, gmasks), (ws, wset, wmasks) in zip(g["staged"],
                                                          w["staged"]):
            assert gs == ws and gset == wset and gmasks == wmasks, (t, gs)
        for gm, wm in zip(g["mirrors"], w["mirrors"]):
            for ga, wa in zip(gm, wm):
                np.testing.assert_array_equal(ga, wa, err_msg=f"tick {t}")
        np.testing.assert_array_equal(g["wire"], w["wire"], err_msg=f"tick {t}")
        assert g["retired"] == w["retired"], t
        assert g["streams"] == w["streams"], t
    assert any(g["streams"][-1] for g in got[-1:])
    # one staging a tick for each bucket touched, whatever the sections
    buckets = 1 if layout == "one-bucket" else 2
    assert stages <= (TICKS + 1) * buckets
    if shape == "many":
        assert sections / stages > 57 / buckets * 0.8


def test_the_benchmarks_reader_divides_the_two_counters_on_the_live_registry():
    """``encode_sections_per_stage``: sections gathered per bucket-wide
    staging; a program without the counters (the parent) reads None."""
    import importlib

    from kcp_tpu.utils.trace import REGISTRY

    read = importlib.import_module(
        "benchmarks.layer_metrics.encode_sections_per_stage").read
    assert read({"registry": {"fused_encoded_rows_total": 9.0}}) is None
    core, owners = fleet_of(30)
    wide = Owner(core, 32)
    for i, o in enumerate(owners + [wide]):
        o.objs["k"] = both(o.S, i)
    names = ("fused_encoded_sections_total", "fused_stage_batches_total")
    snap0 = REGISTRY.snapshot()
    core._encode_sections({o.section: {"k": 3} for o in owners + [wide]})
    core._encode_section(wide.section, {"k": 1})
    snap1 = REGISTRY.snapshot()
    rise = {k: snap1[k] - snap0.get(k, 0.0) for k in names}
    # 31 sections in two buckets, then one by itself: 32 over 3
    assert rise == dict(zip(names, (32.0, 3.0)))
    assert read({"registry": rise}) == pytest.approx(32 / 3)


# -------------------------------------------------------- edge cases


def fleet_of(n: int, s: int = 16):
    core = FusedCore(pipeline="serial")
    return core, [Owner(core, s) for _ in range(n)]


def tick(core: FusedCore, touched: dict) -> None:
    """``touched``: ``{owner: {key: side mask}}``, as one ``_tick``."""
    core._tick([(id(o), side == 2, k, o.section)
                for o, km in touched.items() for k, m in km.items()
                for side in (1, 2) if m & side], time.monotonic())


def test_an_overflow_in_the_middle_of_a_tick_leaves_out_that_section_only():
    core, owners = fleet_of(57)
    for i, o in enumerate(owners):
        o.objs["k"] = both(o.S, i, differ=0)
    odd = owners[28]
    odd.overflow_at = 32
    overflows = []
    real = odd.fused_overflow
    odd.fused_overflow = lambda: (overflows.append(1), real())[1]
    old = odd.section
    stages0 = counter("fused_stage_batches_total")
    sections0 = counter("fused_encoded_sections_total")
    tick(core, {o: {"k": 1} for o in owners})
    assert overflows == [1] and old.released
    assert odd.section.bucket is core.buckets[32] and not odd.section.rows
    assert counter("fused_stage_batches_total") == stages0 + 1
    assert counter("fused_encoded_sections_total") == sections0 + 56
    for o in owners:
        if o is not odd:
            assert o.stream == [("k", DECISION_UPDATE, False)]
    assert odd.stream == []
    # the replay, as the engine enqueues it, lands in the wider bucket
    tick(core, {odd: {"k": 1}})
    assert odd.stream == [("k", DECISION_UPDATE, False)]


def test_a_ragged_section_beside_regular_ones_is_staged_key_by_key():
    core, owners = fleet_of(9, s=32)
    for i, o in enumerate(owners):
        o.objs["a"] = both(o.S, i, differ=0)
        o.objs["b"] = both(o.S, 50 + i)
    odd = owners[4]
    # mid-migration: one vector still at the narrower encoder's width
    narrow = vals(16, 99)
    odd.objs["b"] = (narrow, narrow.copy())
    bucket = odd.section.bucket
    singles = []
    real = bucket.stage
    bucket.stage = lambda *a: (singles.append(a[0]), real(*a))[1]
    stages0 = counter("fused_stage_batches_total")
    core._encode_sections({o.section: {"a": 1, "b": 1} for o in owners})
    del bucket.stage
    rows = odd.section.rows
    # the ragged section: every key, both sides, one row at a time; its
    # neighbours each by themselves
    assert sorted(singles) == sorted([rows["a"], rows["a"], rows["b"], rows["b"]])
    assert counter("fused_stage_batches_total") == stages0 + len(owners) - 1
    padded = np.concatenate([narrow, np.zeros(16, np.uint32)])
    odd.objs["b"] = (padded, padded)
    for o in owners:
        for key, (u, d) in o.objs.items():
            r = o.section.rows[key]
            assert (bucket.up_vals[r] == u).all() and bucket.up_exists[r]
            assert (bucket.down_vals[r] == d).all() and bucket.down_exists[r]
    assert bucket._staged_n == 2 * 2 * len(owners)
    fleet = core._fleet
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    for o in owners:
        assert o.stream == [("a", DECISION_UPDATE, False)]


def test_a_key_touched_on_both_sides_in_one_tick_is_never_acked():
    core, owners = fleet_of(40)
    for i, o in enumerate(owners):
        o.objs["k"] = both(o.S, i, differ=0)
    core._encode_sections({o.section: {"k": 3} for o in owners})
    fleet = core._fleet
    bucket = owners[0].section.bucket
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    # one tick: for half the keys upstream writes a NEW spec while the
    # location's event still echoes the OLD one — equal to the resident
    # up row, which is what the ack lane would copy; for the other half
    # the location's echo comes alone and may ride the ack lane
    old_up = [o.objs["k"][0] for o in owners]
    for i, o in enumerate(owners):
        if i % 2 == 0:
            o.objs["k"] = (vals(o.S, 500 + i), old_up[i].copy())
        else:
            o.objs["k"] = (old_up[i], old_up[i].copy())
    core._encode_sections({o.section: {"k": 3 if i % 2 == 0 else 2}
                           for i, o in enumerate(owners)})
    n = bucket._staged_n
    acked = {int(r) for r, a in zip(bucket._staged_rows[:n],
                                    bucket._staged_ack[:n]) if a}
    both_sides = {o.section.rows["k"] for o in owners[0::2]}
    down_only = {o.section.rows["k"] for o in owners[1::2]}
    assert acked == down_only and not acked & both_sides
    assert n == 2 * len(both_sides) + len(down_only)
    for o in owners:
        del o.stream[:]
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    st = fleet._state
    for i, o in enumerate(owners):
        r = o.section.rows["k"]
        up, down = o.objs["k"]
        assert (np.asarray(st.up_vals[r])[: o.S] == up).all()
        assert (np.asarray(st.down_vals[r])[: o.S] == down).all()
        # every slot of the new spec differs, the status slots too
        assert o.stream == ([("k", DECISION_UPDATE, True)] if i % 2 == 0
                            else [])


def test_a_retirement_a_reuse_by_another_section_and_a_growth_in_one_tick():
    core, (a, b, c) = fleet_of(3)
    fleet = core._fleet
    bucket = a.section.bucket
    for i in range(MIN_ROWS - 4):
        a.objs[i] = both(a.S, 100 + i)
    b.objs.update(b0=both(b.S, 300), b1=both(b.S, 301))
    c.objs.update(c0=both(c.S, 400), c1=both(c.S, 401))
    tick(core, {o: dict.fromkeys(o.objs, 3) for o in (a, b, c)})
    assert bucket.B == MIN_ROWS and bucket._next == MIN_ROWS
    # a row of A's comes free (its tick's wire is dispatched: serial)
    row_freed = a.section.rows[7]
    del a.objs[7]
    tick(core, {a: {7: 3}})
    assert bucket._free == [row_freed] and a.retired == [7]
    uploads0 = fleet.stats["full_uploads"]
    stages0 = counter("fused_stage_batches_total")
    # ONE tick: A retires key 9, B's new key takes A's old row, C's two
    # new keys need rows 64 and 65 (B doubles), B's b0 changes
    row_gone = a.section.rows[9]
    del a.objs[9]
    b.objs["new"] = both(b.S, 500, differ=2)
    b.objs["b0"] = both(b.S, 501, differ=3)
    c.objs.update(c2=both(c.S, 502, differ=4), c3=(vals(c.S, 503), None))
    for o in (a, b, c):
        del o.stream[:]
    tick(core, {a: {9: 3}, b: {"new": 1, "b0": 3}, c: {"c2": 1, "c3": 1}})
    assert counter("fused_stage_batches_total") == stages0 + 1
    assert b.section.rows["new"] == row_freed
    assert bucket.row_owner[row_freed] is b.section
    assert (bucket.status_mask[row_freed] == b._mask).all()
    assert (c.section.rows["c2"], c.section.rows["c3"]) == (MIN_ROWS,
                                                           MIN_ROWS + 1)
    assert bucket.B == 2 * MIN_ROWS
    assert fleet.stats["full_uploads"] == uploads0 + 1
    assert a.retired == [7, 9] and 9 not in a.section.rows
    assert bucket._free == [row_gone] and not bucket._held
    assert a.stream == []
    assert sorted(b.stream) == [("b0", DECISION_UPDATE, False),
                                ("new", DECISION_UPDATE, False)]
    assert sorted(c.stream) == [("c2", DECISION_UPDATE, False), ("c3", 1, False)]
    st = fleet._state
    for o in (a, b, c):
        assert set(o.section.rows) == set(o.objs)
        for key, (up, down) in o.objs.items():
            r = o.section.rows[key]
            assert bool(st.up_exists[r])
            assert bool(st.down_exists[r]) == (down is not None)
            assert (np.asarray(st.up_vals[r])[: o.S] == up).all()
            if down is not None:
                assert (np.asarray(st.down_vals[r])[: o.S] == down).all()
    assert not bool(st.up_exists[row_gone]) and not bool(st.down_exists[row_gone])
    assert int(fleet._seg_ids[row_freed]) == b.section.seg


class EncoderOwner(Owner):
    """An owner whose mask is its encoder's, as the engine's is."""

    def __init__(self, core: FusedCore, enc: BucketEncoder):
        self.enc = enc
        self.asked = 0
        super().__init__(core, enc.capacity)

    def fused_status_mask(self) -> np.ndarray:
        self.asked += 1
        return self.enc.status_mask()

    def fused_encode(self, key):
        obj = self.objs[key]
        vec = self.enc.encode(obj)
        return vec, True, vec, True


def test_a_vocabulary_that_grows_a_status_slot_marks_the_bucket_stale_once():
    core = FusedCore(pipeline="serial")
    enc = BucketEncoder(capacity=16)
    enc.encode({"data": {"a": 1}, "status": {"ready": True}})
    o = EncoderOwner(core, enc)
    other = Owner(core, 16)
    other.objs["x"] = both(16, 1)
    bucket = o.section.bucket
    o.objs["k0"] = {"data": {"a": 2}, "status": {"ready": False}}
    core._encode_sections({o.section: {"k0": 3}, other.section: {"x": 3}})
    fleet = core._fleet
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    assert not bucket._stale and fleet.stats["full_uploads"] == 1
    mask0 = enc.status_mask()
    # ticks that grow nothing: the same array every time, nothing
    # rebuilt, nothing stale
    rebuilt = []
    real = bucket.mark_stale
    bucket.mark_stale = lambda: (rebuilt.append(1), real())[1]
    for n in range(3):
        o.objs["k0"] = {"data": {"a": 3 + n}, "status": {"ready": True}}
        core._encode_sections({o.section: {"k0": 1}})
        assert enc.status_mask() is mask0
    assert rebuilt == [] and not bucket._stale
    # a spec path grows the vocabulary and not the mask: another array
    # of equal content, still nothing stale
    o.objs["k0"] = {"data": {"a": 1, "b": 2}, "status": {"ready": True}}
    core._encode_sections({o.section: {"k0": 1}})
    assert enc.status_mask() is not mask0 and rebuilt == []
    # a status path does: the section's rows are restamped, once
    o.objs["k1"] = {"data": {"a": 1}, "status": {"ready": True, "seen": 4}}
    core._encode_sections({o.section: {"k1": 3}})
    slot = enc.slots["status.seen"]
    assert rebuilt == [1] and bucket._stale
    for key in ("k0", "k1"):
        assert bucket.status_mask[o.section.rows[key], slot]
    assert not bucket.status_mask[other.section.rows["x"], slot]
    core._encode_sections({o.section: {"k0": 1, "k1": 1}})
    core._encode_sections({other.section: {"x": 1}})
    assert rebuilt == [1]
    fleet.dispatch(np.asarray((w := fleet.submit())[0]), w[1])
    assert fleet.stats["full_uploads"] == 2
    row = o.section.rows["k1"]
    assert bool(np.asarray(fleet._state.status_mask[row])[slot])
    # nobody can change the shared mask under the encoder
    with pytest.raises(ValueError):
        enc.status_mask()[0] = True
