#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU, the entry points a user would call:

  phase 0  device      the accelerator JAX finds, versions, compile cache,
                       native library
  phase 1  served      ``kcp start``'s own Server (WAL, controllers, push
                       syncers, backend "tpu") over REST at the reference's
                       target: 1k logical clusters x 50 objects
  phase 2  fused core  a closed churn/reconcile/apply loop at 131,072 resident
                       rows x 64 slots, plus one full-width step held to a
                       numpy oracle written here
  phase 3  pallas      the off-by-default Pallas lane, compiled (not
                       interpreted) and bit-identical to the XLA lanes

``--chips 4`` runs instead the one path that exists only across chips: a
mesh-sharded FusedCore against a single-device one under the same churn.

Every phase is an importable function that takes its sizes as arguments
(tests/test_chip_smoke.py drives them tiny on the CPU). A failed check
raises; nothing is caught and reported as a note. The last line of stdout
is the contract: ``{"ok": true, "device": {...}}``. Without an accelerator
the script exits non-zero and prints no result. Timings printed here are
those of a smoke, for sizing the next piece of work, and are no metric.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shutil
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _cache_entries(path: str | None) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _r, _d, files in os.walk(path))


# --------------------------------------------------------------- phase 0


def phase_device(require: str | None = "tpu",
                 count: int | None = 1) -> tuple[dict, str | None]:
    """Find the device with no help; returns (the contract's device
    record, the compile-cache directory in use). ``require`` is the
    platform the run must be on (None = whatever JAX has, for CPU
    rehearsals)."""
    import importlib.metadata as md

    import jax

    from kcp_tpu import native
    from kcp_tpu.cli import enable_compilation_cache

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    say(f"phase 0 device: platform={d0.platform}")
    say(f"phase 0 device: device_kind={d0.device_kind}")
    say(f"phase 0 device: count={len(devs)}")
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            ver = md.version(pkg)
        except md.PackageNotFoundError:
            ver = "not installed"
        say(f"phase 0 device: {pkg}={ver}")
    if require is not None and d0.platform != require:
        raise SystemExit(
            f"chip_smoke: JAX found platform {d0.platform!r}, not "
            f"{require!r}: no accelerator, no result")
    if count is not None and len(devs) != count:
        raise SystemExit(
            f"chip_smoke: {len(devs)} devices, this run needs {count}")
    cache = enable_compilation_cache()
    say(f"phase 0 device: compile_cache_dir={cache} "
        f"entries_before={_cache_entries(cache)}")
    how, detail = native.status()
    say(f"phase 0 device: native_library={how} ({detail})")
    return device, cache


# --------------------------------------------------------------- phase 1


def _fused_counters() -> dict[str, float]:
    """The serving core's failure and tick counters (process-wide, so the
    phases judge the rise over their own run)."""
    from kcp_tpu.utils.trace import REGISTRY

    return {name: REGISTRY.counter(name).value for name in (
        "fused_step_failures_total", "quarantined_rows",
        "fused_fleet_ticks_total")}


def _check_no_step_failed(before: dict[str, float]) -> float:
    """No fused step failed or quarantined a row since ``before``;
    returns the fleet ticks since then."""
    now = _fused_counters()
    for name in ("fused_step_failures_total", "quarantined_rows"):
        check(now[name] == before[name],
              f"{name} rose by {now[name] - before[name]}: a device step "
              f"failed and was retried or bisected")
    return now["fused_fleet_ticks_total"] - before["fused_fleet_ticks_total"]


def _fan_out(n_threads: int, work) -> None:
    """Run ``work(thread_index)`` on n threads; the first error re-raises."""
    errs: list[BaseException] = []

    def run(i: int) -> None:
        try:
            work(i)
        except Exception as e:  # noqa: BLE001 — re-raised by the caller's thread
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,), name=f"smoke-w{i}")
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]


def _wait(pred, timeout: float, what: str, interval: float = 0.25):
    deadline = time.monotonic() + timeout
    while True:
        got = pred()
        if got:
            return got
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out after {timeout:.0f}s: {what}")
        time.sleep(interval)


def phase_served(n_clusters: int = 1000, objs_per: int = 50, writers: int = 8,
                 seed: int = 0, platform: str | None = "tpu",
                 root: str | None = None, timeout: float = 600.0) -> dict:
    """The served path: the Server ``kcp start`` builds, on a thread of
    this process (the fake:// locations live in its registry, and a child
    holding the chip would leave none for phase 2), driven over HTTP."""
    from kcp_tpu.apis import cluster as capi
    from kcp_tpu.physical import PhysicalRegistry
    from kcp_tpu.server.rest import RestClient
    from kcp_tpu.server.server import Config
    from kcp_tpu.server.threaded import ServerThread
    from kcp_tpu.syncer.core import FusedCore
    from kcp_tpu.syncer.engine import CLUSTER_LABEL
    from kcp_tpu.utils.errors import NotFoundError

    check(objs_per >= 3, "objs_per >= 3: update, delete and status each "
                         "take an object of their own")
    root = root or os.path.join(HERE, ".kcp_tpu", "chip_smoke")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    registry = PhysicalRegistry()
    cfg = Config(durable=True, root_dir=root, tls=False,
                 install_controllers=True, auto_publish_apis=True,
                 resources_to_sync=["configmaps"], syncer_mode="push")
    tenants = [f"t{i:04d}" for i in range(n_clusters)]
    loc = "loc"  # each logical cluster's one location, fake://<tenant>-loc
    say(f"phase 1 served: {n_clusters} logical clusters x {objs_per} "
        f"ConfigMaps, {writers} client threads, seed {seed}, root {root}")

    def cm(name: str, data: dict, labelled: bool = True) -> dict:
        meta = {"name": name, "namespace": "default"}
        if labelled:
            meta["labels"] = {CLUSTER_LABEL: loc}
        return {"apiVersion": "v1", "kind": "ConfigMap", "metadata": meta,
                "data": data}

    def names_of(t: int) -> tuple[random.Random, list[str]]:
        rng = random.Random(seed * 1_000_003 + t)
        return rng, [f"cm-{j:03d}-{rng.getrandbits(32):08x}"
                     for j in range(objs_per)]

    counters = _fused_counters()
    srv = ServerThread(cfg, registry=registry).start(timeout=120)
    ledger: dict[tuple[str, str], dict] = {}  # acknowledged (tenant, name) -> data
    private = {tenants[t] for t in range(0, n_clusters, 100)}
    lock = threading.Lock()
    try:
        base = srv.address
        wild = RestClient(base, cluster="*")

        # ---- bring-up: one Cluster per logical cluster, all Ready
        t0 = time.perf_counter()

        def register(w: int) -> None:
            c = RestClient(base)
            for t in range(w, n_clusters, writers):
                c.cluster = tenants[t]
                c.create(capi.CLUSTERS, capi.new_cluster(
                    loc, kubeconfig=f"fake://{tenants[t]}-{loc}"))
            c.close()

        _fan_out(writers, register)

        def all_ready():
            items, _rv = wild.list(capi.CLUSTERS)
            ready = sum(1 for o in items if capi.is_ready(o)
                        and "configmaps" in capi.synced_resources(o))
            return ready == n_clusters and len(items) == n_clusters

        _wait(all_ready, timeout, f"{n_clusters} Clusters Ready", 0.5)
        t_up = time.perf_counter() - t0
        say(f"phase 1 served: bring-up {t_up:.1f}s "
            f"({n_clusters} logical clusters Ready, syncing configmaps)")

        # ---- load: objs_per labelled ConfigMaps in each, seeded
        t0 = time.perf_counter()

        def load(w: int) -> None:
            c = RestClient(base)
            for t in range(w, n_clusters, writers):
                tenant = tenants[t]
                rng, names = names_of(t)
                c.cluster = tenant
                for name in names:
                    data = {"k0": f"{rng.getrandbits(64):016x}",
                            "k1": f"{rng.getrandbits(64):016x}", "gen": "0"}
                    c.create("configmaps", cm(name, data))
                    with lock:
                        ledger[(tenant, name)] = data
                if tenant in private:
                    c.create("configmaps", cm("private", {"p": "1"},
                                              labelled=False))
            c.close()

        _fan_out(writers, load)
        t_load = time.perf_counter() - t0
        n_written = len(ledger)
        check(n_written == n_clusters * objs_per, f"{n_written} acknowledged")
        say(f"phase 1 served: load {t_load:.1f}s ({n_written} creates "
            f"acknowledged, {n_written / t_load:.0f}/s)")

        def downstream(chunk: range) -> dict[str, dict[str, dict]]:
            """{tenant: {name: object}} of a chunk's downstream stores,
            read on the server's loop (the stores belong to it)."""
            def read():
                out = {}
                for t in chunk:
                    down = registry.resolve(f"fake://{tenants[t]}-{loc}")
                    items, _rv = down.list("configmaps")
                    out[tenants[t]] = {o["metadata"]["name"]: o for o in items}
                return out
            return srv.call(read)

        def all_downstream() -> dict[str, dict[str, dict]]:
            out: dict[str, dict[str, dict]] = {}
            for lo in range(0, n_clusters, 100):
                out.update(downstream(range(lo, min(lo + 100, n_clusters))))
            return out

        def down_matches() -> bool:
            got = all_downstream()
            with lock:
                want = dict(ledger)
            if sum(len(v) for v in got.values()) != len(want):
                return False
            return all(
                (o := got[tenant].get(name)) is not None and o.get("data") == data
                for (tenant, name), data in want.items())

        t0 = time.perf_counter()
        _wait(down_matches, timeout, "every create present downstream", 0.5)
        say(f"phase 1 served: downsync of the load converged "
            f"{time.perf_counter() - t0:.1f}s after the last acknowledgement")

        # ---- a few requests: one update everywhere, a delete in every
        # tenth, a downstream status per logical cluster
        t0 = time.perf_counter()

        def update_wave(w: int) -> None:
            c = RestClient(base)
            for t in range(w, n_clusters, writers):
                tenant = tenants[t]
                _rng, names = names_of(t)
                c.cluster = tenant
                obj = c.get("configmaps", names[0], "default")
                obj["data"] = dict(obj["data"], gen="1", wave=f"{seed}-{t}")
                c.update("configmaps", obj)
                with lock:
                    ledger[(tenant, names[0])] = obj["data"]
            c.close()

        _fan_out(writers, update_wave)
        t_wave = time.perf_counter() - t0
        t0 = time.perf_counter()
        _wait(down_matches, timeout, "update wave present downstream", 0.1)
        t_conv = time.perf_counter() - t0
        say(f"phase 1 served: update wave {n_clusters} updates in "
            f"{t_wave:.1f}s, converged downstream {t_conv:.2f}s after the "
            f"last acknowledgement")

        deleted: list[tuple[str, str]] = []
        c = RestClient(base)
        for t in range(0, n_clusters, 10):
            _rng, names = names_of(t)
            c.cluster = tenants[t]
            c.delete("configmaps", names[1], "default")
            deleted.append((tenants[t], names[1]))
            del ledger[(tenants[t], names[1])]
        _wait(down_matches, timeout, "deletes applied downstream", 0.1)
        say(f"phase 1 served: {len(deleted)} deletes gone downstream")

        def write_statuses(chunk: range) -> None:
            def write():
                for t in chunk:
                    _rng, names = names_of(t)
                    down = registry.resolve(f"fake://{tenants[t]}-{loc}")
                    obj = down.get("configmaps", names[2], "default")
                    obj["status"] = {"observed": True, "n": t}
                    down.update_status("configmaps", obj)
            srv.call(write)

        t0 = time.perf_counter()
        for lo in range(0, n_clusters, 100):
            write_statuses(range(lo, min(lo + 100, n_clusters)))

        def upstream() -> dict[tuple[str, str], dict]:
            items, _rv = wild.list("configmaps")
            return {(o["metadata"]["clusterName"], o["metadata"]["name"]): o
                    for o in items}

        def statuses_up() -> bool:
            up = upstream()
            return all(
                (up.get((tenants[t], names_of(t)[1][2])) or {}).get("status")
                == {"observed": True, "n": t} for t in range(n_clusters))

        _wait(statuses_up, timeout, "downstream statuses upstream", 0.25)
        say(f"phase 1 served: {n_clusters} downstream statuses upsynced in "
            f"{time.perf_counter() - t0:.1f}s")

        # ---- the exact checks
        up = upstream()
        want_keys = set(ledger) | {(t, "private") for t in private}
        check(set(up) == want_keys,
              f"REST read-back: {len(set(up) ^ want_keys)} keys differ")
        for key, data in ledger.items():
            check(up[key]["data"] == data, f"REST read-back differs at {key}")
        got = all_downstream()
        for tenant in tenants:
            want = {n: d for (t, n), d in ledger.items() if t == tenant}
            have = got[tenant]
            check(set(have) == set(want),
                  f"downstream {tenant}: {sorted(set(have) ^ set(want))}")
            for name, data in want.items():
                check(have[name].get("data") == data,
                      f"downstream {tenant}/{name} data differs")
        for tenant, name in deleted:
            check(name not in got[tenant], f"{tenant}/{name} not deleted")
            try:
                c.cluster = tenant
                c.get("configmaps", name, "default")
            except NotFoundError:
                pass
            else:
                raise AssertionError(f"{tenant}/{name} still upstream")
        c.close()
        for tenant in private:
            check("private" not in got[tenant],
                  f"unlabelled object synced to {tenant}")

        ticks = _check_no_step_failed(counters)
        check(ticks > 0, "no fused tick ran: the device path was not served")
        cores = [core for core in FusedCore._instances.values()
                 if core._loop is srv._loop]
        check(len(cores) == 1, f"{len(cores)} fused cores on the server loop")
        fleet = cores[0]._fleet
        check(fleet is not None and fleet._state is not None,
              "the serving core holds no fleet state")
        on = {d.platform for d in fleet._state.up_vals.devices()}
        if platform is not None:
            check(on == {platform}, f"fleet state lives on {on}")
        live = int(np.asarray(fleet._state.up_exists).sum())
        say(f"phase 1 served: rows resident {live} live of B={fleet.B} x "
            f"S={fleet.S} on {sorted(on)}; fused ticks {ticks:.0f}, step "
            f"failures 0, quarantined 0")
        wild.close()
    finally:
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)
    return {"clusters": n_clusters, "objects": n_written,
            "bring_up_s": t_up, "load_s": t_load, "update_conv_s": t_conv,
            "rows_resident": live, "fused_ticks": ticks}


# --------------------------------------------------------------- phase 2


class SyntheticOwner:
    """A SectionOwner with mirror arrays in place of informer caches and
    mirror copies in place of store writes; everything between (queue,
    staging, fused step, pipeline, dispatch) is the serving code. Phase 2,
    the mesh phase and ``__graft_entry__``'s dry run register one on a
    FusedCore."""

    def __init__(self, core, b: int, s: int, seed: int = 7):
        self.core = core
        self.B, self.S = b, s
        self.rng = np.random.default_rng(seed)
        # status slots: the top s//8 columns, as example_state lays out
        mask = np.zeros(s, bool)
        mask[-max(1, s // 8):] = True
        self._mask = mask
        self.section = core.register(self, s)
        bucket = self.section.bucket
        for i in range(b):
            self.section.row_for(i)
        bucket.up_vals[:b] = self.rng.integers(1, 2**32, (b, s), dtype=np.uint32)
        bucket.down_vals[:b] = bucket.up_vals[:b]
        flip = self.rng.random(b) < 0.005
        bucket.down_vals[:b][flip, :1] ^= 1
        bucket.up_exists[:b] = True
        bucket.down_exists[:b] = True
        bucket.mark_stale()
        self.bucket = bucket
        self.patch_rows = 0

    def fused_status_mask(self) -> np.ndarray:
        return self._mask

    def fused_encode(self, key: int):
        b = self.bucket
        return b.up_vals[key], True, b.down_vals[key], True

    def fused_encode_many(self, keys):
        b = self.bucket
        idx = np.fromiter(keys, np.int64, len(keys))
        return (b.up_vals[idx], np.ones(idx.size, bool),
                b.down_vals[idx], np.ones(idx.size, bool))

    def fused_overflow(self) -> None:  # pragma: no cover - fixed vocab
        raise AssertionError("the synthetic vocabulary never grows")

    def fused_apply(self, patches) -> None:
        """The applier seam: sync each patch row downstream and enqueue
        the feedback event."""
        rows = np.fromiter((k for k, _c, _u in patches), np.int32, len(patches))
        self.patch_rows += rows.size
        self.bucket.down_vals[rows] = self.bucket.up_vals[rows]
        self.core.enqueue_many(self.section, True, rows.tolist())

    def emit_churn(self, n: int) -> None:
        """New upstream specs for ``n`` random rows, enqueued key by key
        through the serving work queue."""
        rows = self.rng.choice(self.B, size=n, replace=False)
        self.bucket.up_vals[rows] = self.rng.integers(
            1, 2**32, (n, self.S), dtype=np.uint32)
        self.core.enqueue_many(self.section, False, rows.tolist())


class _CompileCounter:
    """Counts backend compiles through jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


_MIRRORS = ("up_vals", "down_vals", "up_exists", "down_exists")


def _lagging_mirror(fleet_state, bucket, b: int) -> str | None:
    """The first mirror whose resident copy, fetched back from the device,
    differs from the host's over the b live rows (None = all equal)."""
    import jax

    got = jax.device_get(fleet_state)
    s = bucket.up_vals.shape[1]  # the fleet pads slots to its widest bucket
    for name in _MIRRORS:
        dev = getattr(got, name)[:b]
        if not np.array_equal(dev[:, :s] if dev.ndim == 2 else dev,
                              getattr(bucket, name)[:b]):
            return name
    return None


async def _settle(core, bucket, b: int, timeout: float, what: str) -> None:
    """Wait until the loop has drained AND the device agrees: the host
    mirrors converged, nothing queued, staged or in flight, and the
    resident state fetched back from the device (``jax.device_get``) equal
    to the host mirrors, exactly.

    State-based, not time-based: events the controller has drained but not
    yet staged are invisible from outside and show only as a device that
    still lags the host, so the comparison is retried until the deadline.
    A real divergence never heals and fails there, naming the mirror."""
    deadline = time.monotonic() + timeout
    queue, fleet = core.controller.queue, core._fleet
    while True:
        behind = int((bucket.up_vals[:b] != bucket.down_vals[:b]).any(axis=1).sum())
        lag = (f"loop busy (queued {len(queue)}, in flight "
               f"{len(core._inflight)}, dirty {fleet.dirty}, host rows not "
               f"converged {behind}, ticks {bucket.stats['ticks']})")
        if (len(queue) == 0 and not core._inflight and not fleet.dirty
                and not behind and fleet._state is not None):
            name = _lagging_mirror(fleet._state, bucket, b)
            if name is None:
                return
            lag = f"resident {name} on the device differs from the host mirror"
        if time.monotonic() > deadline:
            raise AssertionError(f"{what}: not settled in {timeout:.0f}s: {lag}")
        await asyncio.sleep(0.02)


def _oracle_step(state, deltas, k: int):
    """A vectorised numpy restatement of the host decision oracle
    (syncer/engine.py ``_host_decisions``) with the reference splitter's
    even split for the placement lane — the semantics of one reconcile
    step, written here and independent of kcp_tpu.ops."""
    up, dn = state.up_vals.copy(), state.down_vals.copy()
    ue, de = state.up_exists.copy(), state.down_exists.copy()
    sel_up = deltas.valid & ~deltas.side
    sel_dn = deltas.valid & deltas.side
    up[deltas.idx[sel_up]] = deltas.vals[sel_up]
    ue[deltas.idx[sel_up]] = deltas.exists[sel_up]
    dn[deltas.idx[sel_dn]] = deltas.vals[sel_dn]
    de[deltas.idx[sel_dn]] = deltas.exists[sel_dn]
    mask = state.status_mask if state.status_mask.ndim == 2 \
        else state.status_mask[None, :]
    neq = up != dn
    spec_dirty = (neq & ~mask).any(axis=1)
    status_dirty = (neq & mask).any(axis=1)
    decision = np.zeros(up.shape[0], np.int64)  # 0 noop 1 create 2 update 3 delete
    decision[ue & ~de] = 1
    decision[ue & de & spec_dirty] = 2
    decision[de & ~ue] = 3
    upsync = ue & de & status_dirty
    rows = np.flatnonzero((decision != 0) | upsync)
    # splitter: floor split over the available clusters, the whole
    # remainder on the first available one
    avail = state.avail
    n = avail.sum(axis=1)
    n_safe = np.maximum(n, 1)
    each = state.replicas // n_safe
    rest = state.replicas - each * n_safe
    first = avail & (np.cumsum(avail, axis=1) == 1)
    leaf = np.where(avail, each[:, None] + first * rest[:, None], 0)
    leaf[n == 0] = 0
    p_dirty = (state.current != leaf).any(axis=1)
    matched = ((state.pair_hashes[:, None, :] == state.sel_hashes[None, :, None])
               .any(axis=2) & ue[:, None]).sum()
    stats = np.array([ue.sum(), (decision == 1).sum(), (decision == 2).sum(),
                      (decision == 3).sum(), upsync.sum(), p_dirty.sum(),
                      matched, deltas.valid.sum()], np.int64)
    droots = np.flatnonzero(p_dirty)
    return {"rows": rows[:k], "code": decision[rows[:k]],
            "upsync": upsync[rows[:k]], "overflow": rows.size > k,
            "stats": stats, "roots": droots, "leaf": leaf[droots],
            "up": up, "dn": dn, "ue": ue, "de": de}


def full_width_step(b: int, s: int, r: int, p: int, d: int, k: int,
                    seed: int = 0) -> dict:
    """One ``reconcile_step_packed`` on the default device against the
    numpy oracle: patches, stats, placement segment and the new mirrors."""
    import jax

    from kcp_tpu.models.reconcile_model import (
        example_deltas,
        example_state,
        pack_deltas,
        reconcile_step_packed,
        unpack_patches,
        unpack_placement,
    )

    rng = np.random.default_rng(seed)
    st = example_state(b=b, s=s, r=r, p=p, l=8, c=64, seed=seed)
    # the served layout: per-row masks; and every decision code present
    n_edge = max(1, b // 256)
    edge = rng.permutation(b)[:3 * n_edge]
    st.down_exists[edge[:n_edge]] = False            # -> CREATE
    st.up_exists[edge[n_edge:2 * n_edge]] = False    # -> DELETE
    st.down_vals[edge[2 * n_edge:], -1] ^= 1         # -> status upsync
    hit = rng.permutation(b)[:max(1, b // 64)]
    st.pair_hashes[hit, 0] = st.sel_hashes[hit % st.sel_hashes.shape[0]]
    st = st._replace(
        status_mask=np.broadcast_to(st.status_mask, (b, s)).copy(),
        current=rng.integers(0, 3, (r, p)).astype(np.int32))
    dl = example_deltas(b=b, s=s, d=d, seed=seed + 1)
    want = _oracle_step(st, dl, k)
    check(not want["overflow"], f"oracle overflows K={k}: pick a larger K")

    step = jax.jit(reconcile_step_packed, donate_argnums=(0,),
                   static_argnames=("patch_capacity", "use_pallas", "mesh"))
    t0 = time.perf_counter()
    dev_state = jax.tree.map(jax.device_put, st)
    new_state, wire = step(dev_state, jax.device_put(pack_deltas(dl)),
                           patch_capacity=k)
    wire = np.asarray(wire)
    dt = time.perf_counter() - t0
    idx, code, upsync, overflow, stats = unpack_patches(wire)
    check(not overflow, "device step overflowed")
    check(np.array_equal(idx, want["rows"]), "patch rows differ from oracle")
    check(np.array_equal(code, want["code"]), "patch codes differ from oracle")
    check(np.array_equal(upsync, want["upsync"]), "upsync flags differ")
    check(np.array_equal(stats, want["stats"]),
          f"stats differ: device {stats.tolist()} oracle {want['stats'].tolist()}")
    roots, leaf = unpack_placement(wire, k, p, r)
    check(np.array_equal(roots, want["roots"]), "dirty roots differ")
    check(np.array_equal(leaf, want["leaf"]), "leaf replica counts differ")
    got = jax.device_get(new_state)
    for name, key in (("up_vals", "up"), ("down_vals", "dn"),
                      ("up_exists", "ue"), ("down_exists", "de")):
        check(np.array_equal(getattr(got, name), want[key]),
              f"new state {name} differs from oracle")
    check(set(np.unique(code)) == {0, 1, 2, 3},
          f"decision codes exercised: {np.unique(code)}")
    say(f"phase 2 fused core: full-width step B={b} S={s} R={r} P={p} D={d} "
        f"K={k}: {idx.size} patches, {roots.size} dirty roots, stats "
        f"{stats.tolist()} == numpy oracle ({dt:.1f}s incl. compile)")
    return {"patches": int(idx.size), "stats": stats.tolist()}


def phase_fused_core(b: int = 131072, s: int = 64, churn: int = 768,
                     warmup: int = 24, ticks: int = 100,
                     r: int = 16384, d: int = 1024, k: int = 8192,
                     seed: int = 0, timeout: float = 300.0) -> dict:
    """A closed control loop — FusedCore with the serving defaults and a
    synthetic section owner, churned once per tick — then the full-width
    step."""
    import jax

    from kcp_tpu.syncer.core import FusedCore

    say(f"phase 2 fused core: B={b} S={s} over {b // 13} tenants, {churn} "
        f"new spec events per tick, {warmup} warm-up + {ticks} ticks")
    counters = _fused_counters()
    compiles = _CompileCounter()

    async def loop() -> dict:
        core = FusedCore(batch_window=0.0005)
        check(core.pipeline == "double",
              "not the serving defaults")
        owner = SyntheticOwner(core, b, s, seed=seed + 7)
        bucket = owner.bucket
        bucket.patch_capacity = k
        ack_floor = max(8192, b // 64, 2 * churn)
        bucket.ack_capacity = 1 << (ack_floor - 1).bit_length()
        await core.start()

        async def run(n_ticks: int, sizes) -> float:
            """Churn one batch per core tick for n_ticks ticks — the next
            batch only once the queue has taken the last, so a tick carries
            at most one (plus feedback) and its padded shape stays inside
            what warm-up compiled, however late this pump runs."""
            t0 = time.perf_counter()
            start = seen = bucket.stats["ticks"]
            progress, owed, i = t0, True, 0
            while bucket.stats["ticks"] - start < n_ticks:
                now = time.perf_counter()
                t = bucket.stats["ticks"]
                if t != seen:
                    seen, progress, owed = t, now, True
                elif now - progress > 45:
                    raise AssertionError(
                        f"tick counter stuck at {t} for 45s: no progress")
                if owed and len(core.controller.queue) == 0:
                    owner.emit_churn(min(sizes[i % len(sizes)], b))
                    owed, i = False, i + 1
                await asyncio.sleep(0.0002)
            return time.perf_counter() - t0

        # warm-up takes every delta-batch shape the window can use: the
        # packed wire is padded to a power of two of the tick's entries
        # (at most the churn plus as much unacked feedback), so churn the
        # middle of every such bucket, floor to twice the window's churn
        sizes, bucket_rows = [], 64
        while bucket_rows <= 4 * churn:
            sizes.append(3 * bucket_rows // 4)
            bucket_rows *= 2
        t_warm = await run(warmup, sizes)
        await _settle(core, bucket, b, timeout, "warm-up")
        t_warm += await run(8, [churn])
        c_warm = compiles.n
        t_run = await run(ticks, [churn])
        c_run = compiles.n - c_warm
        n_ticks = bucket.stats["ticks"]
        t0 = time.perf_counter()
        await _settle(core, bucket, b, timeout, "drain")
        t_drain = time.perf_counter() - t0

        # settled: host mirrors converged and the state fetched back from
        # the device equals them
        fleet = core._fleet
        on = {dev.platform for dev in fleet._state.up_vals.devices()}
        stats = dict(fleet.stats)
        await core.stop()
        return {"on": on, "t_warm": t_warm, "t_run": t_run,
                "t_drain": t_drain, "ticks": n_ticks, "c_warm": c_warm,
                "c_run": c_run, "patch_rows": owner.patch_rows,
                "eager": core._eager_collect,
                "stats": stats}

    try:
        out = asyncio.run(loop())
    finally:
        compiles.close()
    say(f"phase 2 fused core: warm-up {out['t_warm']:.1f}s "
        f"({out['c_warm']} compiles), {ticks} ticks in {out['t_run']:.2f}s, "
        f"drain {out['t_drain']:.2f}s, {out['patch_rows']} patch rows applied")
    say(f"phase 2 fused core: state on {sorted(out['on'])} (donated every "
        f"step), eager collection {'on' if out['eager'] else 'off'}, fleet "
        f"stats {out['stats']}")
    _check_no_step_failed(counters)
    check(out["stats"]["step_failures"] == 0 and out["stats"]["quarantined"] == 0,
          f"fleet stats {out['stats']}")
    check(out["c_run"] == 0,
          f"{out['c_run']} compiles after warm-up (patch-capacity overflows: "
          f"{out['stats']['overflows']}): a recompile is a serving stall")
    say("phase 2 fused core: mirrors converged, resident state on the device "
        "== host mirrors, 0 step failures, 0 quarantined, 0 compiles after "
        "warm-up")
    mem = jax.devices()[0].memory_stats() or {}
    say(f"phase 2 fused core: peak_bytes_in_use={mem.get('peak_bytes_in_use')}")
    out["step"] = full_width_step(b, s, r, 8, d, k, seed)
    return out


# --------------------------------------------------------------- phase 3


def phase_pallas(b: int = 131072, s: int = 64, l: int = 8, c: int = 64,
                 r: int = 16384, d: int = 1024, k: int = 8192,
                 block_rows: int = 2048, seed: int = 0,
                 compiled: bool = True) -> dict:
    """The Pallas lane is a kernel on the chip and not an interpreter:
    ``decide_and_match`` bit-identical to the XLA lanes on the same device
    arrays, and the whole step lowers to a ``tpu_custom_call``.
    ``compiled=False`` is the CPU rehearsal (interpreter, no custom call)."""
    import jax
    import jax.numpy as jnp

    from kcp_tpu.models.reconcile_model import (
        example_deltas,
        example_state,
        pack_deltas,
        reconcile_step_packed,
    )
    from kcp_tpu.ops.diff import sync_decisions
    from kcp_tpu.ops.labelmatch import fanout_match
    from kcp_tpu.ops.pallas_kernels import decide_and_match, default_interpret

    check(default_interpret() is (not compiled),
          f"default_interpret()={default_interpret()} on {jax.default_backend()}")
    rng = np.random.default_rng(seed)
    st = example_state(b=b, s=s, r=r, p=8, l=l, c=c, seed=seed, dirty_frac=0.05)
    st.down_exists[rng.permutation(b)[:b // 100 + 1]] = False
    st.up_exists[rng.permutation(b)[:b // 100 + 1]] = False
    st.down_vals[rng.permutation(b)[:b // 50 + 1], -1] ^= 1
    hit = rng.permutation(b)[:b // 16 + 1]
    st.pair_hashes[hit, rng.integers(0, l, hit.size)] = st.sel_hashes[hit % c]

    @jax.jit
    def xla(uv, ue, dv, de, m, ph, sh):
        dec = sync_decisions(uv, ue, dv, de, m)
        counts = (fanout_match(ph, sh) & ue[:, None]).sum(axis=0, dtype=jnp.int32)
        return dec.decision, dec.status_upsync, counts

    for mask_name, mask in (("bucket-wide", st.status_mask),
                            ("per-row", np.broadcast_to(
                                st.status_mask, (b, s)).copy())):
        args = [jax.device_put(x) for x in (
            st.up_vals, st.up_exists, st.down_vals, st.down_exists, mask,
            st.pair_hashes, st.sel_hashes)]
        want = jax.device_get(xla(*args))
        got = jax.device_get(decide_and_match(
            *args, block_rows=min(block_rows, b), interpret=not compiled))
        for name, w, g in zip(("decision", "upsync", "match_counts"), want, got):
            check(w.dtype == g.dtype and np.array_equal(w, g),
                  f"pallas {name} differs from the XLA lanes ({mask_name} mask)")
        say(f"phase 3 pallas: decide_and_match B={b} S={s} L={l} C={c} "
            f"{mask_name} mask bit-identical to the XLA lanes "
            f"(decisions {np.bincount(want[0], minlength=4).tolist()}, "
            f"matches {int(want[2].sum())})")

    st = st._replace(status_mask=np.broadcast_to(st.status_mask, (b, s)).copy())
    step = jax.jit(reconcile_step_packed,
                   static_argnames=("patch_capacity", "use_pallas", "mesh"))
    dev_state = jax.tree.map(jax.device_put, st)
    packed = jax.device_put(pack_deltas(example_deltas(b=b, s=s, d=d, seed=seed + 1)))
    lowered = step.lower(dev_state, packed, patch_capacity=k, use_pallas=True)
    has_call = "tpu_custom_call" in lowered.as_text()
    if compiled:
        check(has_call, "use_pallas=True lowered no tpu_custom_call: the "
                        "step would serve the interpreter or the XLA lanes")
    _s1, wire_p = step(dev_state, packed, patch_capacity=k, use_pallas=True)
    _s2, wire_x = step(dev_state, packed, patch_capacity=k, use_pallas=False)
    check(np.array_equal(np.asarray(wire_p), np.asarray(wire_x)),
          "the step's wire differs between the Pallas and the XLA lanes")
    say(f"phase 3 pallas: reconcile_step_packed(use_pallas=True) "
        f"tpu_custom_call={'present' if has_call else 'absent'}, wire equal "
        f"to the XLA lanes'")
    return {"tpu_custom_call": has_call}


# ------------------------------------------------------------ --chips 4


def phase_mesh(n_devices: int = 4, b: int = 131072, s: int = 64,
               churn: int = 768, steps: int = 16, seed: int = 0,
               timeout: float = 300.0) -> dict:
    """A FusedCore on ``mesh_from_spec(str(n_devices))`` — what ``kcp start
    --mesh`` serves on — and a single-device FusedCore under the same
    seeded churn, in lock step: patch streams byte-equal, the state really
    sharded over the devices, the stats reduction a collective."""
    import hashlib

    import jax

    from kcp_tpu.models.reconcile_model import ack_lane_rows
    from kcp_tpu.parallel.mesh import SLOTS_AXIS, TENANTS_AXIS, mesh_from_spec
    from kcp_tpu.syncer.core import FusedCore

    class Owner(SyntheticOwner):
        def __init__(self, *a, **kw):
            self.stream: list[tuple[int, int, bool]] = []
            super().__init__(*a, **kw)

        def fused_apply(self, patches) -> None:
            self.stream.extend((int(r), int(c), bool(u)) for r, c, u in patches)
            super().fused_apply(patches)

    async def run(mesh) -> dict:
        core = FusedCore(mesh=mesh, batch_window=0.0005)
        owner = Owner(core, b, s, seed=seed + 7)
        bucket = owner.bucket
        await core.start()
        core.kick()  # the first full upload, before any churn
        info: dict = {}
        t0 = time.perf_counter()
        for _ in range(steps):
            await _settle(core, bucket, b, timeout, "mesh step")
            owner.emit_churn(min(churn, b))
            await asyncio.sleep(0.005)
        await _settle(core, bucket, b, timeout, "mesh drain")
        info["seconds"] = time.perf_counter() - t0
        fleet = core._fleet
        check(fleet.stats["step_failures"] == 0
              and fleet.stats["quarantined"] == 0, f"fleet stats {fleet.stats}")
        if mesh is not None:
            up = fleet._state.up_vals
            spec = tuple(up.sharding.spec)
            check(spec == (TENANTS_AXIS, SLOTS_AXIS), f"sharding spec {spec}")
            shards = up.addressable_shards
            devs = {sh.device.id for sh in shards}
            check(len(devs) == n_devices,
                  f"up_vals sits on {len(devs)} devices, not {n_devices}")
            for sh in shards:
                check(sh.data.shape[0] * n_devices == up.shape[0],
                      f"shard of {sh.data.shape} on {sh.device}: not a "
                      f"1/{n_devices} of {up.shape}")
            k = fleet._patch_capacity()

            def struct(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding)

            repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            text = fleet._step.lower(
                jax.tree.map(struct, fleet._state), struct(fleet._seg_ids),
                jax.ShapeDtypeStruct(
                    (1024 + ack_lane_rows(fleet.ack_capacity, s + 2), s + 2),
                    np.uint32, sharding=repl),
                ack_capacity=fleet.ack_capacity, patch_capacity=k, seg_capacity=fleet._seg_capacity,
                use_pallas=False, mesh=mesh).compile().as_text()
            check("all-reduce" in text,
                  "the sharded step holds no all-reduce for the stats")
            info["shards"] = [(sh.device.id, tuple(sh.data.shape))
                              for sh in shards]
            info["collectives"] = sorted(
                {op for op in ("all-reduce", "all-gather", "all-to-all",
                               "collective-permute", "reduce-scatter")
                 if op in text})
        info["ticks"] = fleet.stats["ticks"]
        await core.stop()
        info["stream"] = np.asarray(owner.stream, np.int64).tobytes()
        return info

    say(f"mesh phase: {n_devices} devices, B={b} S={s}, {steps} lock-step "
        f"churn batches of {churn}")
    mesh = mesh_from_spec(str(n_devices))
    sharded = asyncio.run(run(mesh))
    single = asyncio.run(run(None))
    say(f"mesh phase: sharded {sharded['ticks']} ticks in "
        f"{sharded['seconds']:.1f}s, single-device {single['ticks']} ticks in "
        f"{single['seconds']:.1f}s")
    say(f"mesh phase: up_vals shards {sharded['shards']}; collectives in the "
        f"compiled step: {sharded['collectives']}")
    check(len(sharded["stream"]) > 0, "no patches flowed")
    check(sharded["stream"] == single["stream"],
          "patch streams differ between the mesh and the single device")
    sha = hashlib.sha256(sharded["stream"]).hexdigest()[:16]
    say(f"mesh phase: patch streams byte-equal "
        f"({len(sharded['stream']) // 24} patches, sha256 {sha})")
    return {"patches": len(sharded["stream"]) // 24, "sha": sha,
            "shards": sharded["shards"]}


# ------------------------------------------------------------------ main


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh phase and its single-device "
                         "comparison, on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="every name, value and churn schedule derives from it")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device, cache = phase_device(require="tpu", count=args.chips)
    timings: dict[str, float] = {}

    def timed(name: str, fn, **kw) -> None:
        t0 = time.perf_counter()
        fn(**kw)
        timings[name] = time.perf_counter() - t0
        say(f"{name}: PASS in {timings[name]:.1f}s")

    if args.chips == 4:
        timed("mesh phase", phase_mesh, n_devices=4, seed=args.seed)
    else:
        timed("phase 1 served", phase_served, seed=args.seed)
        timed("phase 2 fused core", phase_fused_core, seed=args.seed)
        timed("phase 3 pallas", phase_pallas, seed=args.seed)
    say(f"compile cache {cache}: entries_after={_cache_entries(cache)}")
    say(f"all phases passed in {time.perf_counter() - t_start:.1f}s: "
        + ", ".join(f"{k} {v:.1f}s" for k, v in timings.items()))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
