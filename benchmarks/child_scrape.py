"""What a server in a CHILD process did over the measured window, for the
per-layer readers: run.py snapshots the registry of its own process only,
so a topology that starts a server as a child (a storage frontend, later a
shard or a replica) samples it from outside and hands the rises back
through the load generator's handle, beside ``records``; they reach every
reader as ``ctx["generator"][<key>]`` (benchmarks/README.md).

A sample is the child's ``/metrics`` page (Prometheus text) and its
process's CPU seconds (``/proc/<pid>/stat``), taken at the two edges of
the window. ``rises`` gives ``{"window_s", "cpu_s", "metrics"}`` with
``metrics`` in the form of ``deploy.registry_snapshot``: a counter or
gauge under its name, a histogram's SUM under its name and its COUNT under
``<name>_count`` — what ``phase_means`` and ``counter_ratio`` read; a name
that did not move in the window is left out, as one the child does not
expose. What could not be sampled is None, and a reader gives None for
all of these: never an exception, never a wait.
"""

from __future__ import annotations

import os
import threading
import time
import urllib.request

from benchmarks import deploy, phase_means


def parse_metrics(text: str) -> dict[str, float]:
    """A ``/metrics`` page by name. Bucket lines (the only labelled
    series the program exposes) are left out."""
    out: dict[str, float] = {}
    histograms: set[str] = set()
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _hash, _type, name, kind = line.split(" ", 3)
            if kind.strip() == "histogram":
                histograms.add(name)
            continue
        if not line or line[0] == "#" or "{" in line:
            continue
        name, _, value = line.rpartition(" ")
        try:
            v = float(value)
        except ValueError:
            continue
        if name.endswith("_sum") and name[:-4] in histograms:
            name = name[:-4]
        out[name] = v
    return out


def scrape(address: str, timeout: float = 5.0) -> dict[str, float] | None:
    try:
        with urllib.request.urlopen(address + "/metrics",
                                    timeout=timeout) as resp:
            return parse_metrics(resp.read().decode("utf-8", "replace"))
    except (OSError, ValueError):
        return None


def cpu_seconds(pid: int) -> float | None:
    """User + system seconds of a process, all its threads."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rpartition(")")[2].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def sample(address: str, pid: int) -> dict:
    return {"t": time.monotonic(), "cpu_s": cpu_seconds(pid),
            "metrics": scrape(address)}


def rises(before: dict, after: dict) -> dict:
    a, b = before["metrics"], after["metrics"]
    cpu = (None if before["cpu_s"] is None or after["cpu_s"] is None
           else after["cpu_s"] - before["cpu_s"])
    return {"window_s": after["t"] - before["t"], "cpu_s": cpu,
            "metrics": (None if a is None or b is None else
                        {k: v for k, v in deploy.rise(a, b).items() if v})}


class ScrapedLoadGen:
    """A load generator's handle (deploy.LoadGen) that also samples a
    child at the window's two edges, which it knows after ``go()``, and
    returns the rises under ``key`` beside ``records``."""

    def __init__(self, inner, key: str, take_sample):
        self.inner = inner
        self.key = key
        self.take_sample = take_sample
        self.samples: list[dict] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._at_the_edges, daemon=True)

    def go(self, lead_s: float = 0.3) -> float:
        t_start = self.inner.go(lead_s)
        self._thread.start()
        return t_start

    @property
    def window(self) -> tuple[float, float]:
        return self.inner.window

    def _at_the_edges(self) -> None:
        for edge in self.window:
            if self._stop.wait(max(0.0, edge - time.monotonic())):
                return
            self.samples.append(self.take_sample())

    def result(self, timeout: float) -> dict:
        out = self.inner.result(timeout)
        self._thread.join(timeout=15.0)
        if len(self.samples) == 2:
            out[self.key] = rises(*self.samples)
        return out

    def kill(self) -> None:
        self._stop.set()
        self.inner.kill()


# ------------------------------------------------------------ for readers


def child(ctx: dict, key: str) -> dict:
    return (ctx.get("generator") or {}).get(key) or {}


def mean_ms(ctx: dict, key: str, histogram: str):
    """Mean of one of the child's histograms over the window, ms
    (``phase_means.mean_ms`` on the child's rises), or None."""
    reg = child(ctx, key).get("metrics")
    if reg is None:
        return None
    return phase_means.mean_ms({"registry": reg}, histogram)


def cpu_pct(ctx: dict, key: str):
    """The child's CPU seconds over the window's length, in percent of
    one core, or None."""
    got = child(ctx, key)
    cpu, length = got.get("cpu_s"), got.get("window_s")
    if cpu is None or not length or length <= 0:
        return None
    print(f"[layer] {key}: {cpu:.3f} CPU seconds in {length:.3f} s",
          flush=True)
    return 100.0 * cpu / length
