"""External-storage option: a frontend server serving against another
server's storage (kcp start --store-server — the reference's
--etcd-servers analog, pkg/server/server.go:263-291).

Two full server processes (threads) share one dataset: writes through
either are visible through both, storage semantics (RV conflicts) are
enforced once by the backend, and watches stream through the frontend.
"""

from __future__ import annotations

import asyncio

import pytest

from kcp_tpu.server.rest import RestClient
from kcp_tpu.server.server import Config
from kcp_tpu.server.threaded import ServerThread
from kcp_tpu.store.remote import RemoteStore
from kcp_tpu.utils import errors


@pytest.fixture()
def pair(tmp_path):
    with ServerThread(Config(durable=False, install_controllers=False)) as backend:
        ca = tmp_path / "backend-ca.crt"
        ca.write_bytes(backend.ca_pem)
        with ServerThread(Config(durable=False, install_controllers=False,
                                 store_server=backend.address,
                                 store_ca_file=str(ca))) as frontend:
            yield backend, frontend


def cm(name, cluster, data):
    return {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": name, "namespace": "default",
                         "clusterName": cluster},
            "data": data}


def test_writes_visible_through_both(pair):
    backend, frontend = pair
    fc = RestClient(frontend.address, ca_data=frontend.ca_pem, cluster="t1")
    bc = RestClient(backend.address, ca_data=backend.ca_pem, cluster="t1")

    created = fc.create("configmaps", cm("via-front", "t1", {"a": "1"}))
    assert created["metadata"]["resourceVersion"]
    assert bc.get("configmaps", "via-front", "default")["data"] == {"a": "1"}

    bc.create("configmaps", cm("via-back", "t1", {"b": "2"}))
    assert fc.get("configmaps", "via-back", "default")["data"] == {"b": "2"}

    items, rv = fc.list("configmaps")
    assert {o["metadata"]["name"] for o in items} == {"via-front", "via-back"}
    assert rv > 0


def test_conflicts_enforced_once_by_backend(pair):
    _backend, frontend = pair
    fc = RestClient(frontend.address, ca_data=frontend.ca_pem, cluster="t1")
    obj = fc.create("configmaps", cm("c", "t1", {"v": "1"}))
    stale = dict(obj, data={"v": "stale"})
    fresh = dict(obj, data={"v": "2"})
    fc.update("configmaps", fresh)
    with pytest.raises(errors.ConflictError):
        fc.update("configmaps", stale)
    # delete through the frontend is real
    fc.delete("configmaps", "c", "default")
    with pytest.raises(errors.NotFoundError):
        fc.get("configmaps", "c", "default")


def test_watch_streams_through_frontend(pair):
    backend, frontend = pair

    async def main():
        fc = RestClient(frontend.address, ca_data=frontend.ca_pem, cluster="tw")
        bc = RestClient(backend.address, ca_data=backend.ca_pem, cluster="tw")
        w = fc.watch("configmaps")
        try:
            # prime the stream (RestWatch connects lazily on first read),
            # give the frontend a beat to subscribe against the backend,
            # then write through the BACKEND
            await w.next_batch(0.05)
            await asyncio.sleep(0.3)
            bc.create("configmaps", cm("seen", "tw", {"x": "y"}))
            got = []
            for _ in range(100):
                got.extend(ev for ev in await w.next_batch(0.05))
                if got:
                    break
            assert got and got[0].object["metadata"]["name"] == "seen"
        finally:
            w.close()

    asyncio.run(main())


def test_wildcard_read_passes_through(pair):
    """A frontend forwards '*' single-object reads in ONE round trip; the
    backend resolves the unique owner (or 400s on ambiguity)."""
    backend, frontend = pair
    bc1 = RestClient(backend.address, ca_data=backend.ca_pem, cluster="wa")
    bc2 = RestClient(backend.address, ca_data=backend.ca_pem, cluster="wb")
    bc1.create("configmaps", cm("only-in-wa", "wa", {"o": "1"}))
    bc1.create("configmaps", cm("both", "wa", {}))
    bc2.create("configmaps", cm("both", "wb", {}))

    fw = RestClient(frontend.address, ca_data=frontend.ca_pem, cluster="*")
    got = fw.get("configmaps", "only-in-wa", "default")
    assert got["metadata"]["clusterName"] == "wa"
    with pytest.raises(errors.BadRequestError):
        fw.get("configmaps", "both", "default")
    # wildcard delete over the frontend's HTTP surface resolves the
    # unique owner backend-side too (RestClient itself refuses to *send*
    # wildcard deletes, so issue the raw request the handler serves)
    fw._request("DELETE",
                "/clusters/*/api/v1/namespaces/default/configmaps/only-in-wa")
    with pytest.raises(errors.NotFoundError):
        fw.get("configmaps", "only-in-wa", "default")


def test_expired_watch_window_surfaces_through_frontend(pair):
    """The backend's 410 arrives mid-stream at the frontend; the frontend
    must translate it to its own in-stream ERROR, not a silent drop."""
    backend, frontend = pair
    bc = RestClient(backend.address, ca_data=backend.ca_pem, cluster="tx")
    for i in range(5):
        bc.create("configmaps", cm(f"g{i}", "tx", {}))
    backend.call(backend.server.store._history.clear)
    bc.create("configmaps", cm("last", "tx", {}))

    async def main():
        fc = RestClient(frontend.address, ca_data=frontend.ca_pem, cluster="tx")
        w = fc.watch("configmaps", since_rv=1)
        with pytest.raises(errors.ConflictError):
            await w.next_batch(max_wait=5.0)
        w.close()

    asyncio.run(main())


def test_backend_refusal_surfaces_through_frontend_watch():
    """A backend refusal that is NOT a 410 (here: 403 from a missing
    --store-token against an authz'd backend) must reach the watching
    client as a terminal in-stream Status with the mapped code — not a
    silently dropped connection (handler watch relay).

    tls=False: this path exercises the relay's error mapping, not
    transport security (and the slim test image has no cryptography)."""
    with ServerThread(Config(durable=False, install_controllers=False,
                             authz=True, tls=False)) as backend:
        # no store_token: every relayed verb is rejected 403 by the
        # backend. install_controllers now defaults OFF with
        # store_server, so the frontend still starts cleanly.
        with ServerThread(Config(durable=False, tls=False,
                                 store_server=backend.address)) as frontend:
            assert frontend.server.install_controllers is False

            async def main():
                fc = RestClient(frontend.address, cluster="tz")
                w = fc.watch("configmaps")
                with pytest.raises(errors.ApiError) as exc:
                    await w.next_batch(max_wait=5.0)
                # the real code relayed, not a flattened 500 or a 410
                assert exc.value.code == 403
                assert not isinstance(exc.value, errors.ConflictError)
                w.close()

            asyncio.run(main())


def test_store_server_rejects_inproc_controllers():
    """install_controllers=True with store_server is the event-loop
    hazard (blocking RemoteStore HTTP on the serving loop): hard error
    unless force_remote_controllers explicitly accepts it."""
    from kcp_tpu.server.server import Server

    with pytest.raises(ValueError):
        Server(Config(durable=False, install_controllers=True, tls=False,
                      store_server="http://127.0.0.1:1"))
    # the explicit override constructs (it only relaxes the guard)
    s = Server(Config(durable=False, install_controllers=True, tls=False,
                      force_remote_controllers=True,
                      store_server="http://127.0.0.1:1"))
    assert s.install_controllers is True
    s.store.close()


def test_syncer_through_frontend(pair):
    """Full control-plane integration: a syncer whose UPSTREAM client is
    the frontend (informers ride the frontend's relayed watch streams;
    writes pass through to the backend's store) downsyncs to a local
    physical store and upsyncs status back — the deepest remote-store
    path a controller exercises."""
    from kcp_tpu.client import Client
    from kcp_tpu.store import LogicalStore
    from kcp_tpu.syncer import start_syncer
    from kcp_tpu.syncer.engine import CLUSTER_LABEL

    backend, frontend = pair

    async def main():
        up = RestClient(frontend.address, ca_data=frontend.ca_pem,
                        cluster="tenant-s")
        phys = Client(LogicalStore(), "p")
        syncer = await start_syncer(up, phys, ["configmaps"], "east",
                                    backend="tpu", resync_period=1.5)
        try:
            # create through the BACKEND: the event must reach the
            # syncer's informer via backend -> frontend relay -> syncer
            bc = RestClient(backend.address, ca_data=backend.ca_pem,
                            cluster="tenant-s")
            obj = cm("relayed", "tenant-s", {"k": "v"})
            obj["metadata"]["labels"] = {CLUSTER_LABEL: "east"}
            bc.create("configmaps", obj)

            from helpers import wait_until as settled

            assert await settled(lambda: any(
                o["metadata"]["name"] == "relayed"
                for o in phys.list("configmaps")[0]), 15.0), (
                "downsync never landed")

            # status upsync back through frontend -> backend
            d = phys.get("configmaps", "relayed", "default")
            d["status"] = {"phase": "Synced"}
            phys.update_status("configmaps", d)
            assert await settled(lambda: (
                bc.get("configmaps", "relayed", "default")
                .get("status", {}).get("phase") == "Synced"), 15.0), (
                "status upsync never landed")
        finally:
            await syncer.stop()

    asyncio.run(main())


def test_concurrent_multi_tenant_churn_through_frontend(pair):
    """Parallel writers across many tenants hammer the frontend: the
    store-I/O pool, the per-cluster client locks, and the LRU must hold
    up under concurrency (this is the path the round's thread-safety
    review hardened — same-cluster requests serialize on one kept-alive
    connection, different clusters proceed in parallel)."""
    import threading

    backend, frontend = pair
    tenants = [f"load-{i}" for i in range(12)]
    errors_seen: list[Exception] = []

    def worker(tenant: str) -> None:
        try:
            c = RestClient(frontend.address, ca_data=frontend.ca_pem,
                           cluster=tenant)
            for i in range(15):
                c.create("configmaps", cm(f"o{i}", tenant, {"n": str(i)}))
            for i in range(0, 15, 3):
                o = c.get("configmaps", f"o{i}", "default")
                o["data"] = {"n": "updated"}
                c.update("configmaps", o)
            for i in range(0, 15, 5):
                c.delete("configmaps", f"o{i}", "default")
        except Exception as e:  # noqa: BLE001 — collected and asserted
            errors_seen.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in tenants]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors_seen, errors_seen[:3]
    # every tenant's final state is exact, read back through the BACKEND
    for tenant in tenants:
        bc = RestClient(backend.address, ca_data=backend.ca_pem,
                        cluster=tenant)
        items, _ = bc.list("configmaps")
        names = {o["metadata"]["name"] for o in items}
        assert names == {f"o{i}" for i in range(15) if i % 5}, (tenant, names)
        assert all(o["data"] == {"n": "updated"}
                   for o in items if int(o["metadata"]["name"][1:]) % 3 == 0)


@pytest.mark.parametrize("seed", [2, 9])
def test_differential_frontend_vs_direct(seed, tmp_path):
    """Relay-fidelity fuzz: one seeded op sequence applied THROUGH a
    frontend must leave the backend's store byte-identical (modulo
    uid/timestamps) to the same sequence applied directly — RVs and
    generations included, since ops are synchronous and RV allocation
    order is the op order. Any divergence is a relay bug (routing,
    subresource handling, conflict mapping)."""
    import random

    def apply_ops(client_for):
        rng = random.Random(seed)
        tenants = ["fa", "fb", "fc"]
        for step in range(60):
            t = rng.choice(tenants)
            c = client_for(t)
            name = f"o{rng.randrange(8)}"
            op = rng.random()
            try:
                if op < 0.35:
                    c.create("configmaps", cm(name, t, {"s": str(step)}))
                elif op < 0.6:
                    o = c.get("configmaps", name, "default")
                    o["data"] = {"s": str(step)}
                    c.update("configmaps", o)
                elif op < 0.75:
                    o = c.get("configmaps", name, "default")
                    o["status"] = {"at": str(step)}
                    c.update_status("configmaps", o)
                else:
                    c.delete("configmaps", name, "default")
            except errors.ApiError:
                # not-found / already-exists from our own sequence: part
                # of the fuzz, and must map IDENTICALLY over the relay
                pass

    def dump(server):
        out = []
        root = RestClient(server.address, ca_data=server.ca_pem, cluster="*")
        items, _ = root.list("configmaps")
        for o in items:
            meta = o["metadata"]
            out.append((meta["clusterName"], meta["name"],
                        meta["resourceVersion"], meta.get("generation"),
                        str(o.get("data")), str(o.get("status"))))
        return sorted(out)

    # run A: through a frontend
    with ServerThread(Config(durable=False, install_controllers=False)) as b1:
        ca = tmp_path / "ca1.crt"
        ca.write_bytes(b1.ca_pem)
        with ServerThread(Config(durable=False, install_controllers=False,
                                 store_server=b1.address,
                                 store_ca_file=str(ca))) as fe:
            clients: dict = {}
            apply_ops(lambda t: clients.setdefault(t, RestClient(
                fe.address, ca_data=fe.ca_pem, cluster=t)))
            through_frontend = dump(b1)
    # run B: directly against a fresh backend
    with ServerThread(Config(durable=False, install_controllers=False)) as b2:
        clients = {}
        apply_ops(lambda t: clients.setdefault(t, RestClient(
            b2.address, ca_data=b2.ca_pem, cluster=t)))
        direct = dump(b2)
    assert through_frontend == direct


def test_remote_store_inventory_probes(pair):
    backend, frontend = pair
    store = frontend.server.store
    assert isinstance(store, RemoteStore)
    fc = RestClient(frontend.address, ca_data=frontend.ca_pem, cluster="inv")
    fc.create("configmaps", cm("one", "inv", {}))
    assert "inv" in store.clusters()
    rv1 = store.resource_version
    assert rv1 > 0
    fc.create("configmaps", cm("two", "inv", {}))
    assert store.resource_version > rv1
    assert "configmaps" in store.resources()
