"""Stand a configuration up as a running deployment: the system under
test, as ``kcp start`` builds it, on a thread of the process that holds
the chip — chip_smoke.phase_served's set-up, driven by a configuration
file instead of arguments.

    Server (durable, WAL on, TLS off, controllers on, auto_publish_apis,
    syncer_mode push) + PhysicalRegistry of fake:// locations
    + one agent per location: the shape's ``AGENT``, a class of the module
    the shape names in ``AGENT_MODULE`` (benchmarks.agents by default)

This is the default topology. A configuration names another with a
``deployment`` key: a module with a ``Deployment`` class that offers what
run.py, sweep.py, compare.py and controls.py call (benchmarks/README.md
has the list); ``load`` below finds it.

Everything the harness takes from the program is imported here and in
benchmarks/compare.py: the server, its in-process client, the registry of
fake locations, the fused core's counters.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys
import time

from benchmarks import shapes

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load(config: dict):
    """The configuration's topology: the ``Deployment`` class of the module
    its ``deployment`` key names, this one by default."""
    return importlib.import_module(
        config.get("deployment", "benchmarks.deploy")).Deployment


def wait_for(pred, timeout: float, what: str, interval: float = 0.1):
    deadline = time.monotonic() + timeout
    while True:
        got = pred()
        if got:
            return got
        if time.monotonic() > deadline:
            raise RuntimeError(f"timed out after {timeout:.0f}s: {what}")
        time.sleep(interval)


def chunks(seq: list, n: int):
    for i in range(0, len(seq), n):
        yield seq[i:i + n]


def registry_snapshot() -> dict[str, float]:
    """Every counter and gauge of the program's registry by name, and
    every histogram's sum (count x mean), so that a per-layer reader can
    take the rise of any of them over the window."""
    from kcp_tpu.utils.trace import REGISTRY

    return {name: (v["count"] * v["mean"] if isinstance(v, dict) else v)
            for name, v in REGISTRY.snapshot().items()}


def rise(before: dict[str, float], after: dict[str, float]) -> dict[str, float]:
    """after - before; a metric first seen after ``before`` rose from 0."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class Deployment:
    def __init__(self, config: dict, seed: int, out_dir: str):
        self.cfg = config
        self.seed = int(seed)
        self.out_dir = out_dir
        self.root = os.path.join(out_dir, "root")
        self.shape = shapes.load(config["shape"])
        self.tenants = shapes.tenant_names(config["logical_clusters"])
        self.locations = shapes.location_names(config["locations_per_cluster"])
        self.population = shapes.population(
            self.shape, self.seed, config["logical_clusters"],
            config["resident_per_cluster"], self.locations)
        self.srv = None
        self.registry = None
        self.agents: list = []
        self.counters0: dict[str, float] = {}

    # ------------------------------------------------------------ bring-up

    def start(self) -> None:
        from kcp_tpu.physical import PhysicalRegistry
        from kcp_tpu.server.server import Config
        from kcp_tpu.server.threaded import ServerThread

        shutil.rmtree(self.root, ignore_errors=True)
        os.makedirs(self.root)
        self.registry = PhysicalRegistry()
        self.counters0 = registry_snapshot()
        cfg = Config(durable=True, root_dir=self.root, tls=False,
                     install_controllers=True, auto_publish_apis=True,
                     resources_to_sync=list(self.cfg["resources_to_sync"]),
                     syncer_mode="push")
        self.srv = ServerThread(cfg, registry=self.registry).start(timeout=120)

    def bring_up(self, say=print) -> None:
        """The whole set-up, in order, one line of timing each."""
        agent = self.agent_class()
        steps = (("server up", self.start),
                 (f"{len(self.tenants) * len(self.locations)} locations Ready",
                  self.register),
                 (f"{len(self.population)} residents populated",
                  self.populate),
                 (f"agents started ({agent.__module__}.{agent.__qualname__})",
                  self.start_agents),
                 ("residents converged", self.settle),
                 (f"warm bursts {self.cfg.get('warm_bursts', [])}", self.warm))
        for what, step in steps:
            t = time.monotonic()
            step()
            say(f"set-up: {what} {time.monotonic() - t:.1f}s")

    def fake(self, tenant: str, loc: str) -> str:
        return f"fake://{tenant}-{loc}"

    def register(self, timeout: float = 600.0) -> None:
        """One Cluster object per (logical cluster, location), through the
        server's in-process client on its loop; wait until every one is
        Ready and syncing the configuration's resources."""
        from kcp_tpu.apis import cluster as capi

        mc = self.srv.server.client
        pairs = [(t, l) for t in self.tenants for l in self.locations]

        def create(chunk):
            for tenant, loc in chunk:
                mc.cluster_client(tenant).create(
                    capi.CLUSTERS, capi.new_cluster(loc, self.fake(tenant, loc)))

        for chunk in chunks(pairs, 100):
            self.srv.call(create, chunk)
        synced = set(self.cfg["resources_to_sync"])

        def ready() -> bool:
            def count():
                items, _rv = mc.list(capi.CLUSTERS)
                return sum(1 for o in items if capi.is_ready(o)
                           and synced <= set(capi.synced_resources(o)))
            return self.srv.call(count) == len(pairs)

        wait_for(ready, timeout, f"{len(pairs)} Clusters Ready", 0.25)

    def agent_class(self):
        """The location's controller: the shape's ``AGENT``, found in the
        module the shape names (``AGENT_MODULE``)."""
        mod = importlib.import_module(
            getattr(self.shape, "AGENT_MODULE", "benchmarks.agents"))
        return getattr(mod, self.shape.AGENT)

    def start_agents(self) -> None:
        cls = self.agent_class()
        made = [cls(self.registry.resolve(self.fake(t, l)))
                for t in self.tenants for l in self.locations]

        async def start(batch):
            for a in batch:
                await a.start()

        for batch in chunks(made, 100):
            self.srv.submit(start(batch))
        self.agents = made

    def populate(self) -> None:
        mc = self.srv.server.client
        items = list(self.population.items())

        def create(chunk):
            for (tenant, _name), body in chunk:
                mc.cluster_client(tenant).create(self.shape.RESOURCE, body)

        for chunk in chunks(items, 500):
            self.srv.call(create, chunk)

    def converged(self, bodies: dict[tuple[str, str], dict]) -> bool:
        """Every one of ``bodies`` shows upstream what its write waits
        for; read key by key from the store on the server's loop (no
        copy, no list of the world)."""
        from kcp_tpu.utils.errors import NotFoundError

        mc = self.srv.server.client
        shape = self.shape

        def read() -> bool:
            for (tenant, name), body in bodies.items():
                try:
                    o = mc.cluster_client(tenant).get(
                        shape.RESOURCE, name, shape.NAMESPACE)
                except NotFoundError:
                    return False
                if shape.observe(o) != shape.want(body):
                    return False
            return True

        return self.srv.call(read)

    def settle(self, timeout: float = 600.0) -> None:
        wait_for(lambda: self.converged(self.population), timeout,
                 "the resident population converged", 0.25)

    def warm(self, timeout: float = 120.0) -> None:
        """Compile every delta-batch shape the window can meet: for each
        size in the configuration's ``warm_bursts``, create that many
        extra objects in one turn of the server's loop, change them all,
        delete them all — each a burst the fused core packs into one
        batch of that size. The residents are not touched."""
        mc = self.srv.server.client
        rng = shapes.seed_rng(self.seed, 5)
        for n in self.cfg.get("warm_bursts", []):
            bodies = {}
            for i in range(n):
                tenant = self.tenants[i % len(self.tenants)]
                name = f"warm-{n}-{i:05d}"
                bodies[(tenant, name)] = self.shape.new(name, rng, self.locations)

            def create():
                for (tenant, _n), body in bodies.items():
                    mc.cluster_client(tenant).create(self.shape.RESOURCE, body)

            def update():
                for key in bodies:
                    bodies[key] = self.shape.mutate(bodies[key], rng)
                    mc.cluster_client(key[0]).update(self.shape.RESOURCE,
                                                     bodies[key])

            def delete():
                for (tenant, name) in bodies:
                    mc.cluster_client(tenant).delete(
                        self.shape.RESOURCE, name, self.shape.NAMESPACE)

            self.srv.call(create)
            wait_for(lambda: self.converged(bodies), timeout,
                     f"warm burst of {n} creates", 0.05)
            self.srv.call(update)
            wait_for(lambda: self.converged(bodies), timeout,
                     f"warm burst of {n} updates", 0.05)
            self.srv.call(delete)
            touched = sorted({t for t, _n in bodies})
            wait_for(lambda: not any(
                name.startswith("warm-")
                for objs in self.downstream(touched).values()
                for items in objs.values()
                for name in (o["metadata"]["name"] for o in items)),
                timeout, f"warm burst of {n} deletes", 0.05)

    def downstream(self, tenants: list[str]) -> dict[str, dict[str, list[dict]]]:
        """{tenant: {location: [objects]}} read on the server's loop (the
        fake stores belong to it), a hundred logical clusters a turn."""
        out: dict[str, dict[str, list[dict]]] = {}

        def read(chunk):
            got = {}
            for tenant in chunk:
                got[tenant] = {}
                for loc in self.locations:
                    down = self.registry.resolve(self.fake(tenant, loc))
                    items, _rv = down.list(self.shape.RESOURCE)
                    got[tenant][loc] = items
            return got

        for chunk in chunks(tenants, 100):
            out.update(self.srv.call(read, chunk))
        return out

    # ------------------------------------------------------------- traffic

    def loadgen_spec(self, traffic: dict, seed: int, seconds: float) -> dict:
        """What a generator kind is told: seven keys it has always had,
        and the whole configuration under ``config``."""
        return {"server": self.srv.address, "shape": self.cfg["shape"],
                "seed": int(seed), "seconds": float(seconds),
                "tenants": self.cfg["logical_clusters"],
                "per_tenant": self.cfg["resident_per_cluster"],
                "locations": self.locations, "traffic": traffic,
                "config": self.cfg}

    def loadgen(self, traffic: dict, seed: int, seconds: float, tag: str):
        """Start the load generator's process (which never imports JAX
        and is pinned to the CPU besides) and wait until it is ready."""
        spec = self.loadgen_spec(traffic, seed, seconds)
        spec_path = os.path.join(self.out_dir, f"loadgen-{tag}.spec.json")
        out_path = os.path.join(self.out_dir, f"loadgen-{tag}.out.json")
        spec["population_file"] = os.path.join(
            self.out_dir, f"loadgen-{tag}.population.json")
        with open(spec["population_file"], "w") as f:
            json.dump([[t, n, body] for (t, n), body in
                       self.population.items()], f)
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             "--spec", spec_path, "--out", out_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True)
        line = proc.stdout.readline().strip()
        if line != "ready":
            proc.kill()
            proc.wait()
            raise RuntimeError(f"load generator said {line!r}, not 'ready'")
        return LoadGen(proc, out_path, traffic, seconds)

    # ----------------------------------------------------------------- end

    def fleet(self) -> dict:
        """B and S of the serving core's fleet state and where it lives."""
        import numpy as np

        from kcp_tpu.syncer.core import FusedCore

        cores = [c for c in FusedCore._instances.values()
                 if c._loop is self.srv._loop]
        if len(cores) != 1:
            raise RuntimeError(f"{len(cores)} fused cores on the server loop")
        fleet = cores[0]._fleet
        if fleet is None or fleet._state is None:
            raise RuntimeError("the serving core holds no fleet state")
        on = sorted({d.platform for d in fleet._state.up_vals.devices()})
        return {"B": int(fleet.B), "S": int(fleet.S), "on": on,
                "live": int(np.asarray(fleet._state.up_exists).sum())}

    def agent_errors(self) -> int:
        return sum(a.errors for a in self.agents)

    def stop(self) -> None:
        if self.srv is not None:
            async def stop_agents(batch):
                for a in batch:
                    await a.stop()
            try:
                for batch in chunks(self.agents, 100):
                    self.srv.submit(stop_agents(batch))
            finally:
                self.srv.stop()
                self.srv = None
        shutil.rmtree(self.root, ignore_errors=True)


class LoadGen:
    """Handle on the generator's process: ``go`` fixes the start instant
    (CLOCK_MONOTONIC is shared), ``result`` waits for its records."""

    def __init__(self, proc, out_path: str, traffic: dict, seconds: float):
        self.proc = proc
        self.out_path = out_path
        self.traffic = traffic
        self.seconds = seconds
        self.t_start = None

    def go(self, lead_s: float = 0.3) -> float:
        self.t_start = time.monotonic() + lead_s
        self.proc.stdin.write(f"go {self.t_start!r}\n")
        self.proc.stdin.flush()
        return self.t_start

    @property
    def window(self) -> tuple[float, float]:
        a = self.t_start + self.traffic["warmup_s"]
        return a, a + self.seconds

    def result(self, timeout: float) -> dict:
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise RuntimeError("the load generator did not finish in time")
        if rc != 0:
            raise RuntimeError(f"the load generator exited with {rc}")
        with open(self.out_path) as f:
            out = json.load(f)
        if out.get("jax_imported"):
            raise RuntimeError("the load generator's process imported JAX")
        return out

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
