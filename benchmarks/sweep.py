#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: one set-up, then
each rate for ``--seconds`` (10 by default) inside one process.

    python3 benchmarks/sweep.py --workload syncer-1k.steady --seed 1 \
        --rates 50,100,150,200,300,400,600

A rate is sustained when nothing failed, the generator was not late
(p95 under 50 ms) and neither lateness nor convergence time grew over
the window (the second half's median convergence is under 1.5x the
first half's). The knee is the highest sustained rate; the traffic file
gets four fifths of it, rounded down to a multiple of 10. Not a
benchmark run: prints rows, no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="50,100,150,200,300,400,600")
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import run as runmod

    _manifest, cell, config, traffic = runmod.resolve(args.workload,
                                                      args.rehearse)
    device, _ = runmod.device_record(args.platform, cell["chips"])

    from kcp_tpu.cli import enable_compilation_cache

    from benchmarks import deploy, reference, stats

    enable_compilation_cache()
    print(f"device {device}", flush=True)
    out_dir = os.path.join(HERE, ".out")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    dep = deploy.load(config)(config, args.seed, out_dir)
    rows = []
    try:
        dep.bring_up()
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            tr = dict(traffic, rate_per_s=rate)
            lg = dep.loadgen(tr, args.seed + 1 + i, args.seconds, f"sweep{i}")
            lg.go()
            w0, w1 = lg.window
            out = lg.result(timeout=args.seconds + 180)
            recs = out["records"]
            ops = [r for r in recs if w0 <= r["due"] < w1 and not r.get("aux")]
            timed = [r for r in ops if r["kind"] != "delete"]
            conv = [r for r in timed if r["seen"] is not None]
            failed = len(timed) - len(conv) + sum(
                1 for r in ops if r["kind"] == "delete" and r["acked"] is None)
            mid = (w0 + w1) / 2

            def p(rs, q, f):
                vals = [f(r) * 1e3 for r in rs]
                return stats.percentile(vals, q) if vals else float("nan")

            lat = lambda r: r["seen"] - r["due"]  # noqa: E731
            late = lambda r: r["sent"] - r["due"]  # noqa: E731
            first = [r for r in conv if r["due"] < mid]
            second = [r for r in conv if r["due"] >= mid]
            row = {
                "offered_per_s": rate,
                "converged_per_s": sum(1 for r in recs if r["seen"] is not None
                                       and w0 <= r["seen"] < w1) / args.seconds,
                "failed": failed,
                "conv_p50_ms": p(conv, 50, lat), "conv_p95_ms": p(conv, 95, lat),
                "conv_p50_first_half_ms": p(first, 50, lat),
                "conv_p50_second_half_ms": p(second, 50, lat),
                "late_p95_ms": p([r for r in ops if r["sent"]], 95, late),
                "late_p95_second_half_ms": p(
                    [r for r in ops if r["sent"] and r["due"] >= mid], 95, late),
                "ack_p50_ms": p([r for r in ops if r["acked"]], 50,
                                lambda r: r["acked"] - r["sent"]),
            }
            row["sustained"] = bool(
                failed == 0 and row["late_p95_ms"] < 50.0
                and row["conv_p50_second_half_ms"]
                < 1.5 * row["conv_p50_first_half_ms"])
            rows.append(row)
            print("sweep " + json.dumps(row), flush=True)
            dep.population, _uncertain = reference.final_state(
                dep.population, recs)
            time.sleep(2.0)  # let any backlog of the last rate drain
    finally:
        dep.stop()
        shutil.rmtree(out_dir, ignore_errors=True)
    good = [r["offered_per_s"] for r in rows if r["sustained"]]
    knee = max(good) if good else None
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": None if knee is None
                      else int(knee * 0.8 // 10 * 10)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
