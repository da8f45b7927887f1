"""The ``frontend`` topology: the default deployment (benchmarks/deploy.py:
``kcp start``'s Server with the chip, controllers, WAL and the ``fake://``
locations, on a thread of this process) as the storage BACKEND, and one
stateless frontend in front of it, started as a user starts it:

    python -m kcp_tpu.cli.kcp start --store-server http://<backend>
        --no-install-controllers --no-tls --syncer-mode none
        --in-memory --listen-port 0

as a CHILD process pinned to the CPU (``scenarios/topology.spawn_server``,
the program's own spawner). The tenants see the frontend only: the load
generator's writes and its watch, and the comparison's REST read-back
(``srv.address``), go through the tier that acknowledged them. Set-up
(register, populate, settle, warm), the downstream stores, the fleet state
and the counters stay the backend's.

The frontend is started as soon as the backend serves, beside
``register``, and waited for before the load generator starts. Its
``/metrics`` and CPU seconds are sampled at the window's edges by the load
generator's handle (benchmarks/child_scrape.py) and reach the readers as
``ctx["generator"]["frontend"]``.
"""

from __future__ import annotations

import signal
import subprocess
import threading
import time
import types
import urllib.request

from benchmarks import child_scrape, deploy


class _Backend(deploy.Deployment):
    """The default deployment, which says when its server is up and
    tells the load generator another address than its own."""

    def __init__(self, config, seed, out_dir, on_up, tenant_address):
        super().__init__(config, seed, out_dir)
        self._on_up = on_up
        self._tenant_address = tenant_address

    def start(self) -> None:
        super().start()
        self._on_up(self.srv.address)

    def loadgen_spec(self, traffic, seed, seconds) -> dict:
        return dict(super().loadgen_spec(traffic, seed, seconds),
                    server=self._tenant_address())


class Deployment:
    def __init__(self, config: dict, seed: int, out_dir: str):
        if config.get("frontends") != 1:
            raise SystemExit("frontend_deploy: the load generator talks to "
                             "one address: `frontends` must be 1, not "
                             f"{config.get('frontends')!r}")
        self.cfg = config
        self.backend = _Backend(config, seed, out_dir, self._spawn,
                                lambda: self.address)
        self.tenants = self.backend.tenants
        self.locations = self.backend.locations
        self.shape = self.backend.shape
        self.proc = None
        self.address = None
        self._spawner = None
        self._spawn_error = None
        self._spawn_s = 0.0
        self._sigterm = None

    # ------------------------------------------------ what the backend is

    @property
    def population(self) -> dict:
        return self.backend.population

    @population.setter
    def population(self, value: dict) -> None:
        self.backend.population = value

    @property
    def counters0(self) -> dict:
        return self.backend.counters0

    def downstream(self, tenants: list[str]) -> dict:
        return self.backend.downstream(tenants)

    def fleet(self) -> dict:
        return self.backend.fleet()

    def agent_errors(self) -> int:
        return self.backend.agent_errors()

    # ------------------------------------------------------- the frontend

    @property
    def srv(self):
        """Where a tenant reads and writes: the frontend."""
        return types.SimpleNamespace(address=self.address)

    def _spawn(self, backend_address: str) -> None:
        """Start the child on a thread, so that its interpreter's start
        runs beside the backend's ``register``."""
        from kcp_tpu.scenarios.topology import spawn_server

        def run():
            t = time.monotonic()
            try:
                self.proc, self.address = spawn_server(
                    ["--store-server", backend_address,
                     *self.cfg["frontend_args"]])
                # a talkative child must not block on a full pipe
                threading.Thread(target=_drain, args=(self.proc.stdout,),
                                 daemon=True).start()
            except Exception as e:  # noqa: BLE001 — raised in bring_up
                self._spawn_error = e
            self._spawn_s = time.monotonic() - t

        self._spawner = threading.Thread(target=run, daemon=True)
        self._spawner.start()

    def _ready(self) -> bool:
        if self.proc.poll() is not None:
            raise RuntimeError(f"the frontend exited with {self.proc.poll()}")
        try:
            with urllib.request.urlopen(self.address + "/readyz",
                                        timeout=2.0) as resp:
                return resp.status == 200
        except OSError:
            return False

    def bring_up(self, say=print) -> None:
        if threading.current_thread() is threading.main_thread():
            # a run that is told to end (a time limit's SIGTERM) unwinds
            # through its caller's ``finally`` to ``stop()``: the child
            # would otherwise serve on, with nobody to end it
            self._sigterm = signal.signal(signal.SIGTERM, _exit_on_sigterm)
        self.backend.bring_up(say)
        t = time.monotonic()
        self._spawner.join()
        if self._spawn_error is not None:
            raise RuntimeError(
                f"the frontend did not start: {self._spawn_error}")
        deploy.wait_for(self._ready, 60.0, "the frontend's /readyz")
        say(f"set-up: frontend {self.address} (pid {self.proc.pid}) over "
            f"{self.backend.srv.address} served after {self._spawn_s:.1f}s "
            f"beside the set-up, waited {time.monotonic() - t:.1f}s more")

    def loadgen(self, traffic: dict, seed: int, seconds: float, tag: str):
        address, pid = self.address, self.proc.pid
        return child_scrape.ScrapedLoadGen(
            self.backend.loadgen(traffic, seed, seconds, tag), "frontend",
            lambda: child_scrape.sample(address, pid))

    # ----------------------------------------------------------------- end

    def stop(self) -> None:
        """End the child in every outcome (also when bring-up raised),
        then the backend."""
        try:
            if self._spawner is not None:
                self._spawner.join()
            if self.proc is not None and self.proc.poll() is None:
                self.proc.terminate()
                try:
                    self.proc.wait(timeout=10.0)
                except subprocess.TimeoutExpired:
                    self.proc.kill()
                    self.proc.wait()
        finally:
            self.backend.stop()
            if self._sigterm is not None:
                signal.signal(signal.SIGTERM, self._sigterm)
                self._sigterm = None


def _exit_on_sigterm(signum, _frame) -> None:
    raise SystemExit(128 + signum)


def _drain(pipe) -> None:
    for _line in pipe:
        pass
