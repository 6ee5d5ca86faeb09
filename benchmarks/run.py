#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Finds everything by name: the cell's configuration file and
builder (``builders/<builder>.py``), its traffic file and driver
(``traffic/<traffic>.json``, ``drivers/<driver>.py``), and one reader per
per-layer metric (``layer_metrics/<metric>.py``). Set-up (build, load,
compile, warm-up) ends where the measured window starts; the comparison with
the plain reference runs after the window has closed, the peak memory has
been read and the program's state is freed. The last line of standard output
is the result. See ``README.md`` beside this file.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def note(msg: str) -> None:
    print(f"bench [{time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, rehearse: bool) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # cells that run but are not admitted yet are found the same way
    with open(os.path.join(HERE, "candidates.json")) as f:
        waiting = json.load(f)
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        known = {e["name"] for e in bench[part]}
        bench[part] = bench[part] + [e for e in waiting[part]
                                     if e["name"] not in known]
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    if rehearse:
        cfg.update(cfg.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
            "per_layer": [m for m in bench["per_layer"] if reported(m)]}


def place_caches() -> dict:
    """Every cache inside the checkout, at fixed paths; the JAX cache where
    ``JAX_COMPILATION_CACHE_DIR`` says if it is set."""
    import jax

    jax_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not jax_dir:
        jax_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", jax_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no plan cache: it is keyed by the graph's content, every seed makes
    # another graph, and a 10M-atom plan is 0.74 GB on disk for a run
    os.environ.pop("HG_PLAN_CACHE", None)
    return {"jax": jax_dir, "aot": os.path.join(ROOT, ".aot_cache")}


class CompileCount:
    """Compiles and cache loads, counted where JAX reports them."""

    MISS = "/jax/compilation_cache/cache_misses"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n = {self.MISS: 0, self.HIT: 0}
        self.programs: list = []       # (when, name, seconds)
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on(self, name: str, **_kw) -> None:
        if name in self.n:
            self.n[name] += 1

    def _on_secs(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.programs.append((time.perf_counter(), kw.get("fun_name"),
                                  secs))

    def since(self, t0: float) -> list:
        """Programs compiled, or loaded from the cache, since ``t0``."""
        return [[name, secs] for t, name, secs in self.programs if t >= t0]

    def read(self) -> dict:
        return {"compiled": self.n[self.MISS], "loaded": self.n[self.HIT]}


class GcWatch:
    """The interpreter's garbage collections, timed: a full collection over
    a store's millions of objects stops every thread of the process."""

    def __init__(self):
        import gc

        self.pauses: list = []          # (when, generation, seconds)
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.pauses.append((now, info["generation"], now - self._t0))

    def since(self, t0: float) -> dict:
        mine = [(g, s) for t, g, s in self.pauses if t >= t0]
        full = [s for g, s in mine if g == 2]
        return {"collections": len(mine), "full": len(full),
                "full_s": sum(full), "seconds": sum(s for _, s in mine),
                "longest_s": max([s for _, s in mine], default=0.0)}


def main(argv=None, also=None) -> int:
    """One run. ``also(driver, got)`` is for the tests beside the benchmark
    (the control): it is given the answers the comparison was given, and
    what it returns is printed under ``also`` in the result."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; refused unless the caller "
                         "set JAX_PLATFORMS=cpu; reports no device metric")
    ap.add_argument("--selfcheck", action="store_true",
                    help="check the trace reducer and the byte functions "
                         "against known answers; needs no chip")
    args = ap.parse_args(argv)
    if args.selfcheck:
        from harness import selfcheck

        return selfcheck.main()
    if not args.workload:
        ap.error("--workload is required")
    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("bench: --rehearse is the CPU rehearsal; set JAX_PLATFORMS=cpu "
              "to ask for it", file=sys.stderr)
        return 2
    # the program, before anything is printed: a directory that holds only
    # the benchmark fails here, with no result
    import hypergraphdb_tpu  # noqa: F401
    import jax

    spec = load_cell(args.workload, args.rehearse)
    cell, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    caches = place_caches()
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} device(s) of platform {dev.platform}",
              file=sys.stderr)
        return 3
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    note(f"platform: {dev.platform}, device_kind: {dev.device_kind}, "
         f"count: {len(devices)}; caches {caches}")
    compiles, gcs = CompileCount(), GcWatch()

    setup: dict = {"checkout": ROOT}
    sut = load_module("builders", cfg["builder"]).build(cfg, args.seed, setup)
    note(f"built: { {k: v for k, v in setup.items() if k != 'checkout'} }")
    driver = load_module("drivers", traffic["driver"]).Driver(
        sut, cfg, traffic, args.seed, setup)
    t0 = time.perf_counter()
    driver.warm()
    setup["warm_s"] = time.perf_counter() - t0
    setup["compiles"] = compiles.read()
    setup_s = time.perf_counter() - T_START
    note(f"warm; set-up {setup_s:.1f} s, compiles {setup['compiles']}")

    # ---- the measured window
    trace_dir = os.path.join(ROOT, ".bench_trace")
    before, t_window = compiles.read(), time.perf_counter()
    if args.trace:
        from hypergraphdb_tpu.obs.device import profile

        shutil.rmtree(trace_dir, ignore_errors=True)
        with profile(trace_dir) as on:
            if not on:
                raise RuntimeError("the profiler did not start")
            t0 = time.perf_counter()
            window = driver.run(args.seconds)
            traced_s = time.perf_counter() - t0
    else:
        window = driver.run(args.seconds)
    after = compiles.read()
    window["compiles_in_window"] = after["compiled"] - before["compiled"]
    window["programs_in_window"] = compiles.since(t_window)
    window["gc_in_window"] = gcs.since(t_window)
    note(f"window closed: {window['window_s']:.2f} s, "
         f"{window['attempted']} attempted, {window['failed']} failed")
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    device["memory_limit_bytes"] = stats.get("bytes_limit")

    # ---- the comparison, once the program's state is freed
    t0 = time.perf_counter()
    got = driver.collect()
    collect_s = time.perf_counter() - t0
    compared = driver.check(got)
    check_s = time.perf_counter() - t0
    extra = also(driver, got) if also is not None else None
    # (a number without a limit says how much was compared)
    checked = {k: v for k, (v, lim) in compared.items() if lim is None}
    compared = {k: vl for k, vl in compared.items() if vl[1] is not None}
    correct = all(value <= limit for value, limit in compared.values())

    # ---- the metrics of this run
    metrics: dict = {}
    out: dict = {}
    if args.trace:
        trace = {"devices": 0}
        if dev.platform == "tpu":
            from harness import trace_reduce

            t0 = time.perf_counter()
            trace = trace_reduce.reduce_file(
                trace_reduce.newest_xplane(trace_dir), traced_s)
            note(f"trace reduced in {time.perf_counter() - t0:.1f} s")
            device["busy_s"], device["window_s"] = trace["busy_s"], traced_s
            out["breakdown"] = trace["breakdown"]
            out["device_modules"] = [
                [k, v, trace["module_runs"][k]] for k, v in sorted(
                    trace["modules"].items(), key=lambda kv: -kv[1])[:8]]
        ctx = {"cell": cell, "config": cfg, "traffic": traffic,
               "window": window, "setup": setup, "trace": trace,
               "device": device}
        for m in spec["per_layer"]:
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    if args.rehearse:
        # a CPU run's timings never stand under a metric's name
        out["rehearsal_values"], metrics = metrics, {}

    out = {
        "correct": correct, "attempted": window["attempted"],
        "failed": window["failed"], "metrics": metrics, "device": device,
        **out,
        "workload": args.workload, "seed": args.seed, "rehearsal": args.rehearse,
        "window_s": window["window_s"], "check_s": check_s,
        "collect_s": collect_s,
        "compiles_in_window": window["compiles_in_window"],
        "programs_in_window": window["programs_in_window"],
        "gc_in_window": window["gc_in_window"],
        "setup": {k: v for k, v in setup.items() if k != "checkout"},
        "generator": window.get("generator"),
        "latency_ms_by_kind": window.get("latency_ms_by_kind"),
        "counters": window.get("counters"), "checked": checked,
        "also": extra,
        "compared": {k: {"value": v, "limit": lim}
                     for k, (v, lim) in compared.items()},
    }
    if window.get("counters"):
        print(json.dumps({"window_counters": window["counters"]}), flush=True)
    for name, (value, limit) in compared.items():
        print(f"bench: compared {name} = {value} (limit {limit})",
              file=sys.stderr)
    print(f"bench: correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
