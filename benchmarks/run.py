#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, the generator kind, the
object shape, the topology and every metric are found by NAME:
BENCHMARK.json -> configs/<config>.json, traffic/<traffic>.json,
generators/<kind>.py, shapes/<shape>.py, the configuration's
``deployment`` module (benchmarks/deploy.py by default),
end_to_end/<metric>.py, layer_metrics/<metric>.py. Nothing in this file
names a cell, a configuration or a deployment class.

A run is: set-up (server, locations Ready, population, agents, warm-up of
every shape, ``warmup_s`` of the cell's own traffic unmeasured) -> the
measured window -> ``cooldown_s`` of the same traffic, then drain -> the
comparison -> (traced run only) the reduction of the profiler slice ->
one JSON line. ``--trace 1`` runs the same set-up, traffic and
comparison; the profiler is open only for a slice inside the window.

Arguments the driver's command never carries: ``--platform cpu`` with
``--rehearse`` (tiny sizes of the configuration's ``rehearsal`` block, for
the CPU sandbox; the line then says ``"platform": "cpu"``), ``--control
<name>`` (benchmarks/controls.py), ``--manifest <file>`` (another
manifest than BENCHMARK.json: benchmarks/tests rehearse toy cells).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SLICE_START_S = 5.0  # the profiler slice: 5 s, starting 5 s into the window
SLICE_S = 5.0


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS:6.1f}s] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload: str, rehearse: bool = False,
            manifest_file: str = "BENCHMARK.json") -> tuple[dict, dict, dict, dict]:
    """(manifest, cell, configuration, traffic) by name; ``rehearse``
    lays each file's ``rehearsal`` block over it."""
    manifest = load_json(REPO, manifest_file)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no cell {workload!r} in {manifest_file} "
                         f"(has {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(REPO, configs[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearse:
        config = dict(config, **config["rehearsal"])
        traffic = dict(traffic, **traffic.get("rehearsal", {}))
    return manifest, cell, config, traffic


def metric_names(manifest: dict, section: str, cell: str) -> list[str]:
    return [m["name"] for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def generator_extras(out: dict) -> dict:
    """Everything the generator handed back besides its records: what it
    measured or counted itself, for the readers (``ctx["generator"]``)."""
    return {k: v for k, v in out.items() if k != "records"}


def read_metrics(package: str, names: list[str], units: dict, ctx: dict) -> dict:
    out = {}
    for name in names:
        reader = importlib.import_module(f"benchmarks.{package}.{name}")
        value = reader.read(ctx)
        if value is None:
            say(f"metric {name}: nothing to read in this run, left out")
            continue
        out[name] = {"value": float(value), "unit": units[name]}
    return out


class CompileCounter:
    """Backend compiles, through jax.monitoring (chip_smoke._CompileCounter)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


class GcClock:
    """Seconds this process spends inside the cyclic collector."""

    def __init__(self):
        self.total = 0.0
        self.long: list[tuple[float, float, int]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            dt = time.monotonic() - self._t0
            self.total += dt
            if dt >= 0.05:
                self.long.append((self._t0, dt, info.get("generation", -1)))


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def device_record(platform: str, chips: int) -> tuple[dict, object]:
    """The device as JAX reports it; no accelerator, or fewer chips than
    the cell asks for, ends the run with no result."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != platform:
        raise SystemExit(f"run.py: JAX found platform {d0.platform!r}, this "
                         f"run needs {platform!r}: no result")
    if len(devs) < chips:
        raise SystemExit(f"run.py: {len(devs)} devices, the cell needs {chips}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}, d0


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def take_slice(trace_dir: str, start: float, length: float) -> tuple[float, float]:
    """Open the profiler for one slice, from this (the main) thread, with
    options under which the Python tracer is off and a failure to start
    raises. Returns the host instants around it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.raise_error_on_start_failure = True
    sleep_until(start)
    t0 = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t1 = time.monotonic()
    sleep_until(t1 + length)
    t2 = time.monotonic()
    jax.profiler.stop_trace()
    t3 = time.monotonic()
    say(f"trace: start_trace {t1 - t0:.2f}s, open {t2 - t1:.2f}s, "
        f"stop_trace {t3 - t2:.2f}s")
    return t1, t2


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default="")
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    if args.platform == "cpu" and not args.rehearse:
        raise SystemExit("run.py: --platform cpu is for --rehearse only")

    manifest, cell, config, traffic = resolve(args.workload, args.rehearse,
                                              args.manifest)
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}

    device, _d0 = device_record(args.platform, cell["chips"])
    import jax

    from kcp_tpu.cli import enable_compilation_cache

    from benchmarks import (compare, controls, deploy, reduce_trace, shapes,
                            stats)

    cache = enable_compilation_cache()
    say(f"device {device}, compile cache {cache}")
    compiles, gcc = CompileCounter(), GcClock()
    out_dir = os.path.join(HERE, ".out")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    if args.control:
        controls.install(args.control, shapes.load(config["shape"]))
        say(f"CONTROL {args.control}: a guarantee is broken underneath; "
            f"this run must come out not correct")

    topology = deploy.load(config)
    dep = topology(config, args.seed, out_dir)
    say(f"topology {topology.__module__}.{topology.__qualname__}")
    say(f"cell {cell['name']}: seed {args.seed}, window {args.seconds:g}s, "
        f"{config['logical_clusters']} logical clusters x "
        f"{config['locations_per_cluster']} locations x "
        f"{config['resident_per_cluster']} resident {config['shape']}s, "
        f"traffic {traffic}")
    lg = None
    try:
        dep.bring_up(say)
        say(f"set-up: compiles so far {compiles.n}")
        lg = dep.loadgen(traffic, args.seed, args.seconds, "run")
        if args.control:
            controls.arm()
        lg.go()
        w0, w1 = lg.window
        setup_s = w0 - T_PROCESS
        sleep_until(w0)
        reg0, comp0, gc0 = deploy.registry_snapshot(), compiles.n, gcc.total
        say(f"window open; set-up was {setup_s:.1f}s")
        trace_dir = os.path.join(out_dir, "trace")
        if args.trace:
            s0 = min(SLICE_START_S, args.seconds / 3.0)
            take_slice(trace_dir, w0 + s0, min(SLICE_S, args.seconds / 3.0))
        sleep_until(w1)
        reg1, comp1, gc1 = deploy.registry_snapshot(), compiles.n, gcc.total
        say("window closed")
        out = lg.result(timeout=traffic["cooldown_s"] + traffic["deadline_s"] + 120)
        lg = None

        # ---- the generator's stamps
        records, extras = out["records"], generator_extras(out)
        ops = [r for r in records if w0 <= r["due"] < w1]
        main_ops = [r for r in ops if not r.get("aux")]
        timed = [r for r in main_ops if r["kind"] != "delete"]
        lat = [(r["seen"] - r["due"]) * 1e3 for r in timed
               if r["seen"] is not None]
        failed_timed = [r for r in timed if r["seen"] is None]
        failed = failed_timed + [r for r in ops if r["kind"] == "delete"
                                 and r["acked"] is None]
        for r in failed[:5]:
            say(f"failed: {r['kind']} {r['key']} due+{r['due'] - w0:.2f}s "
                f"acked={'yes' if r['acked'] else 'no'} error={r['error']}")
        say(f"stamps: {len(main_ops)} operations due in the window "
            f"({len(timed)} timed, {len(lat)} converged, {len(failed)} "
            f"failed); generator extras {extras}")
        if lat:
            say("percentiles of due->seen, ms, over "
                f"{len(lat)} converged + {len(failed_timed)} failed "
                "(samples beyond each): " + ", ".join(
                    f"p{q:g}={stats.percentile_with_failed(lat, len(failed_timed), q, traffic['deadline_s'] * 1e3):.1f}"
                    f" ({stats.samples_beyond(len(timed), q)})"
                    for q in (50, 75, 90, 95, 97, 99, 99.9, 100)))
            by_second: dict[int, list[float]] = {}
            for r in timed:
                if r["seen"] is not None:
                    by_second.setdefault(int(r["due"] - w0), []).append(
                        (r["seen"] - r["due"]) * 1e3)
            worst = sorted(by_second.items(), key=lambda kv: -max(kv[1]))[:5]
            say("slowest seconds of the window (second, operations due, "
                "max ms): " + str([(s, len(v), round(max(v))) for s, v in
                                   sorted(worst)]))
        window_rise = deploy.rise(reg0, reg1)
        checks, fleet = compare.judge(
            dep, records, window_rise, args.platform,
            deploy.rise(dep.counters0, deploy.registry_snapshot()),
            drain_s=10.0 if args.control else 60.0)
        for c in checks:
            say(c.line())
        correct = all(c.ok for c in checks)
        if gcc.long:
            say(f"collector: pauses of 50 ms or more (s into the window, s, "
                f"generation): "
                f"{[(round(a - w0, 2), round(d, 3), g) for a, d, g in gcc.long if w0 <= a < w1]}")

        # ---- metrics
        ctx = {"ops": main_ops, "all_ops": records, "timed": lat,
               "n_failed_timed": len(failed_timed),
               "beyond_ms": traffic["deadline_s"] * 1e3, "window": (w0, w1),
               "seconds": args.seconds, "setup_s": setup_s,
               "registry": window_rise, "compiles": comp1 - comp0,
               "gc": gc1 - gc0, "fleet": fleet,
               "device_kind": device["kind"], "trace": None,
               "generator": extras}
        device["memory_peak_bytes"] = memory_peak(jax.devices())
        line = {"correct": correct, "attempted": len(main_ops),
                "failed": len(failed)}
        if args.trace:
            prefix = ("/device:TPU:" if args.platform == "tpu" else "/host:CPU")
            tr = reduce_trace.reduce(reduce_trace.find_xplane(trace_dir),
                                     device_prefix=prefix,
                                     any_line=args.platform != "tpu")
            ctx["trace"] = tr
            say(f"trace: planes {tr['planes']}, busy {tr['busy_s']:.4f}s of "
                f"{tr['window_s']:.4f}s, {tr['steps']} fused steps")
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            line["metrics"] = read_metrics(
                "layer_metrics",
                metric_names(manifest, "per_layer", cell["name"]), units, ctx)
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
        else:
            line["metrics"] = read_metrics(
                "end_to_end",
                metric_names(manifest, "end_to_end", cell["name"]), units, ctx)
        line["device"] = device
        line["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                   "ok": c.ok} for c in checks}
    finally:
        if lg is not None:
            lg.kill()
        dep.stop()
        shutil.rmtree(out_dir, ignore_errors=True)
    for c in checks:
        print(c.line(), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
