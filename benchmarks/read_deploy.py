"""The ``read`` topology: the default deployment (benchmarks/deploy.py),
whose load generator's handle also JUDGES THE READS. The generator kind
``read_mostly`` offers background reads from reader processes of its own
and leaves their records in files of JSON lines (its extras' ``read_files``;
benchmarks/generators/read_mostly.py says what a record holds). When the
generator's output comes back, ``JudgedLoadGen.result()``

- builds the plain reference's log of writes from the seeded population
  and the generator's ``records`` (benchmarks/k8s_load_read_reference.py
  ``WriteLog``: key, body, sent, acknowledged, acknowledged
  resourceVersion);
- reads every line of every file and has the reference judge it
  (``judge``: ``get_after_ack``, ``list_snapshot``, ``paged_list_snapshot``,
  ``table``, ``list_then_watch``);
- puts the reads' STAMPS (the records without their answers) under
  ``reads`` beside ``records`` — every per-layer reader finds them as
  ``ctx["generator"]["reads"]`` — and the verdict under ``read_verdict``:
  reads judged, mismatches (the first of them as strings), reads that
  ended in an error, reads that took longer than the traffic's
  ``deadline_s``, reads LOST (the schedule is a pure function of the
  seed: what it planned less the lines the readers wrote), answers the
  reference could not pin to one body, 410 restarts of a page walk.

**How the verdict reaches** ``correct``: ``compare.judge`` is not a
topology's to edit and has no check for reads, so this topology adds the
number of mismatches, of errors, of reads past the deadline and of
reads lost to ``agent_errors()`` (check ``agent_errors``, limit 0), and
prints the first three of each. The probes of the sampled WRITES take the other
seam (the shape's ``evidence_mismatches``: check
``converged_for_wrong_values``).
"""

from __future__ import annotations

import json
import time

from benchmarks import deploy
from benchmarks import k8s_load_read_reference as ref
from benchmarks.generators import read_mostly

STAMPS = ("verb", "scope", "tenant", "name", "due", "sent", "done", "bytes",
          "items", "pages", "status", "restarts", "error")


class Reads(list):
    """The reads' stamps. Tens of thousands of them: printed (run.py
    prints a generator's extras) as their count."""

    def __repr__(self) -> str:
        return f"<{len(self)} reads>"


def stamps(read: dict) -> dict:
    out = {k: read.get(k) for k in STAMPS}
    watch = (read.get("answer") or {}).get("watch")
    if watch:
        out["watch"] = {k: watch.get(k) for k in ("sent", "head", "hold_end",
                                                  "closed")}
        out["watch"]["events"] = len(watch.get("events") or ())
    return out


def judge_files(log: ref.WriteLog, files: list[str], deadline_s: float,
                keep: int = 3) -> tuple[Reads, dict]:
    """Every read of every file against the reference: (stamps, verdict)."""
    reads = Reads()
    mismatches: list[str] = []
    errors: list[str] = []
    late: list[str] = []
    verdict = {"judged": 0, "mismatches": 0, "errors": 0, "late": 0,
               "undetermined": 0, "restarts": 0}
    for path in files:
        with open(path) as f:
            for line in f:
                read = json.loads(line)
                who = f"{read['verb']} {read['tenant']}/{read.get('name') or ''}"
                if read.get("error"):
                    verdict["errors"] += 1
                    errors.append(f"{who}: {read['error']}")
                if read["done"] - read["due"] > deadline_s:
                    verdict["late"] += 1
                    late.append(f"{who}: {read['done'] - read['due']:.2f} s "
                                f"after it was due")
                found, undetermined = ref.judge(log, read)
                verdict["judged"] += 1
                verdict["mismatches"] += len(found)
                verdict["undetermined"] += bool(undetermined)
                verdict["restarts"] += read.get("restarts") or 0
                if len(mismatches) < keep:
                    mismatches += [f"{who}: {m}" for m in found]
                stamp = stamps(read)
                stamp["undetermined"] = bool(undetermined)
                reads.append(stamp)
    verdict["examples"] = {"mismatches": mismatches[:keep],
                           "errors": errors[:keep], "late": late[:keep]}
    return reads, verdict


class JudgedLoadGen:
    """A load generator's handle (deploy.LoadGen) whose ``result()``
    judges the reads the generator left in files."""

    def __init__(self, inner, dep):
        self.inner = inner
        self.dep = dep

    def go(self, lead_s: float = 0.3) -> float:
        return self.inner.go(lead_s)

    @property
    def window(self) -> tuple[float, float]:
        return self.inner.window

    def result(self, timeout: float) -> dict:
        out = self.inner.result(timeout)
        files = out.get("read_files")
        if files is None:
            return out  # a kind that does not read
        t = time.monotonic()
        log = ref.WriteLog(self.dep.population, out["records"])
        reads, verdict = judge_files(
            log, files, float(self.inner.traffic.get("deadline_s", 10.0)))
        # every read the schedule planned has one line: a read a reader
        # dropped would otherwise be missing from every tail and count
        planned = read_mostly.planned(self.inner.traffic, self.inner.seconds)
        verdict["planned"] = planned
        verdict["lost"] = abs(planned - verdict["judged"])
        out["reads"], out["read_verdict"] = reads, verdict
        bad = (verdict["mismatches"] + verdict["errors"] + verdict["late"]
               + verdict["lost"])
        self.dep.read_problems += bad
        print(f"reads: {verdict['judged']} of {planned} planned judged by "
              f"the reference in {time.monotonic() - t:.1f}s: "
              f"{verdict['lost']} lost, {verdict['mismatches']} "
              f"mismatches, {verdict['errors']} errors, {verdict['late']} "
              f"past the deadline, {verdict['undetermined']} undetermined, "
              f"{verdict['restarts']} walks restarted", flush=True)
        for kind in ("mismatches", "errors", "late"):
            for text in verdict["examples"][kind]:
                print(f"reads: {kind}: {text}", flush=True)
        return out

    def kill(self) -> None:
        self.inner.kill()


class Deployment(deploy.Deployment):
    def __init__(self, config: dict, seed: int, out_dir: str):
        super().__init__(config, seed, out_dir)
        self.read_problems = 0

    def loadgen(self, traffic: dict, seed: int, seconds: float, tag: str):
        return JudgedLoadGen(super().loadgen(traffic, seed, seconds, tag),
                             self)

    def agent_errors(self) -> int:
        return super().agent_errors() + self.read_problems
