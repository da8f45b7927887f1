"""A topology in a module of its own: a configuration's ``deployment`` key
names this module and the harness takes its ``Deployment``.

It is NOT a subclass of ``deploy.Deployment``: it offers exactly the names
benchmarks/README.md lists under "What a topology offers" and nothing else,
so a whole run through run.py, compare.py and controls.py proves that the
list is all they call (anything more raises AttributeError here). Behind
those names it stands the default server up (a real second topology is a
later PR's to bring) and says that it did, so that a rehearsal can tell
which class ran."""

import types

from benchmarks import deploy


class Deployment:
    def __init__(self, config: dict, seed: int, out_dir: str):
        self._inner = deploy.Deployment(config, seed, out_dir)
        self.tenants = self._inner.tenants
        self.locations = self._inner.locations
        self.shape = self._inner.shape

    @property
    def population(self) -> dict:
        return self._inner.population

    @population.setter
    def population(self, value: dict) -> None:
        self._inner.population = value

    @property
    def srv(self):
        return types.SimpleNamespace(address=self._inner.srv.address)

    @property
    def counters0(self) -> dict:
        return self._inner.counters0

    def bring_up(self, say=print) -> None:
        say("toy topology: bringing up "
            f"{len(self.tenants)} logical clusters")
        self._inner.bring_up(say)

    def loadgen(self, traffic: dict, seed: int, seconds: float, tag: str):
        return self._inner.loadgen(traffic, seed, seconds, tag)

    def downstream(self, tenants: list[str]) -> dict:
        return self._inner.downstream(tenants)

    def fleet(self) -> dict:
        return self._inner.fleet()

    def agent_errors(self) -> int:
        return self._inner.agent_errors()

    def stop(self) -> None:
        self._inner.stop()
