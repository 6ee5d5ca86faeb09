#!/usr/bin/env bash
# Performance-plane gate: the Pallas gather's differential suite
# (hg_gather_or == the XLA gather, bit for bit), the hgperf suites
# (runtime perf sentinel + bench envelope/diff), an AOT-cache cold/warm
# smoke over a REAL ServeRuntime, the bench --diff live gate (a recorded
# c6 mini-run diffs clean against itself; the committed
# injected-regression fixture pair must exit nonzero), and a live
# sentinel drill (seeded serve.launch slowdown on a real runtime fires
# exactly one incident with the flight window + profiler capture on
# disk).
#
# Sits beside lint.sh (AST hazards), verify.sh (jaxpr ground truth),
# chaos.sh (fault injection), and obs.sh (telemetry): this one gates the
# performance plane's correctness contracts.
#
# Usage: tools/perf.sh [extra pytest args]
#   tools/perf.sh -k gather            # differential suite only
set -uo pipefail
cd "$(dirname "$0")/.."

JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
    tests/test_aot_cache.py \
    tests/test_pallas_gather.py \
    tests/test_perf_sentinel.py \
    tests/test_bench_envelope.py \
    -q -m 'not slow' -p no:cacheprovider "$@"
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "tools/perf.sh: differential/perf suites failed (exit $rc)" >&2
    exit "$rc"
fi

# -- AOT cold/warm smoke: a fresh process over a populated cache must
#    reach first dispatch with zero compiles of the warmed buckets ------------
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'PY'
import subprocess
import sys
import tempfile

cache = tempfile.mkdtemp(prefix="hg_perf_aot_")
code = f"""
import json
import numpy as np
from hypergraphdb_tpu import HyperGraph
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime

g = HyperGraph()
nodes = list(g.add_nodes_bulk([f"n{{i}}" for i in range(60)]))
r = np.random.default_rng(0)
for i in range(120):
    ts = r.choice(nodes, size=2, replace=False)
    g.add_link([int(t) for t in ts], value=i)
rt = ServeRuntime(g, ServeConfig(buckets=(4, 8), max_linger_s=0.001,
                                 top_r=8, aot_cache_dir={cache!r}))
res = rt.submit_bfs(int(nodes[0]), max_hops=2).result(timeout=120)
print("AOT " + json.dumps(rt.stats_snapshot()["aot"]))
rt.close()
g.close()
"""

def run():
    import json
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600)
    for line in proc.stdout.splitlines():
        if line.startswith("AOT "):
            return json.loads(line[4:])
    raise SystemExit(f"aot smoke subprocess failed (rc={proc.returncode}):"
                     f"\n{proc.stderr[-2000:]}")

import shutil

try:
    cold = run()
    warm = run()
finally:
    shutil.rmtree(cache, ignore_errors=True)  # multi-MB executables
assert cold["misses"] >= 2 and cold["puts"] >= 2, f"cold never compiled: {cold}"
assert warm["misses"] == 0, f"warm process recompiled: {warm}"
assert warm["disk_hits"] >= 2, f"warm process missed the disk cache: {warm}"
print(f"tools/perf.sh smoke: cold compiled {cold['misses']} buckets "
      f"({cold['compile_s']}s), warm process hit {warm['disk_hits']} from "
      f"disk with zero compiles — AOT cache OK")
PY
smoke_rc=$?
if [ "$smoke_rc" -ne 0 ]; then
    echo "tools/perf.sh: AOT cold/warm smoke failed (exit $smoke_rc)" >&2
    exit "$smoke_rc"
fi

# -- bench --diff live gate: record a c6 mini-run, diff it against itself
#    (identical files MUST exit 0), then the committed injected-regression
#    fixture pair MUST exit nonzero — the contract the real-TPU sweep and
#    CI both lean on ----------------------------------------------------------
DIFF_TMP="$(mktemp -d -t hg_perf_diff_XXXXXX)"
trap 'rm -rf "$DIFF_TMP"' EXIT
BENCH_RECORD_DIR="$DIFF_TMP" BENCH_C6_TAG=perfgate \
BENCH_C6_ENTITIES=2000 BENCH_C6_LINKS=4000 BENCH_C6_REQUESTS=64 \
BENCH_C6_BASELINE_N=16 BENCH_C6_COLD=0 \
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -c "import bench; bench._config_c6()" >/dev/null
rc=$?
if [ "$rc" -ne 0 ] || [ ! -f "$DIFF_TMP/BENCH_C6_perfgate.json" ]; then
    echo "tools/perf.sh: c6 mini-run failed to record (exit $rc)" >&2
    exit 1
fi
python bench.py --diff "$DIFF_TMP/BENCH_C6_perfgate.json" \
    "$DIFF_TMP/BENCH_C6_perfgate.json" >/dev/null
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "tools/perf.sh: --diff of identical recordings exited $rc (want 0)" >&2
    exit 1
fi
python bench.py --diff tests/perf_fixtures/BENCH_C6_base.json \
    tests/perf_fixtures/BENCH_C6_regressed.json >/dev/null
rc=$?
if [ "$rc" -ne 1 ]; then
    echo "tools/perf.sh: --diff of regression fixtures exited $rc (want 1)" >&2
    exit 1
fi
echo "tools/perf.sh: bench --diff gate OK (self-diff clean, injected regression caught)"

# -- live sentinel drill: a REAL runtime with a seeded serve.launch
#    slowdown (sleeping when= hook — latency injection, zero errors) must
#    fire exactly ONE perf_drift incident, with the flight window dump and
#    the bounded profiler capture in the incident dir -------------------------
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'PY'
import json
import os
import tempfile
import time

import numpy as np

from hypergraphdb_tpu import HyperGraph
from hypergraphdb_tpu.fault import global_faults
from hypergraphdb_tpu.obs.flight import FlightRecorder
from hypergraphdb_tpu.obs.http import runtime_health
from hypergraphdb_tpu.obs.perf import PerfSentinel
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime

incident_dir = tempfile.mkdtemp(prefix="hg_perf_drill_")
flight = FlightRecorder(incident_dir=incident_dir, min_dump_interval_s=0.0)
sentinel = PerfSentinel(baseline={"lanes": {}}, flight=flight,
                        windows=(2.0, 6.0), min_samples=4,
                        eval_interval_s=0.0, profile_s=1.0)

g = HyperGraph()
nodes = list(g.add_nodes_bulk([f"n{i}" for i in range(80)]))
r = np.random.default_rng(0)
for i in range(160):
    ts = r.choice(nodes, size=2, replace=False)
    g.add_link([int(t) for t in ts], value=i)
rt = ServeRuntime(g, ServeConfig(buckets=(4,), max_linger_s=0.001,
                                 top_r=16, perf=sentinel))

def soak(n):
    for i in range(n):
        rt.submit_bfs(int(nodes[i % len(nodes)]), max_hops=2).result(
            timeout=120)

soak(4)          # warmup: compiles must not pollute the healthy digest
time.sleep(2.1)  # ... so let it age out of the short measurement window
soak(24)         # healthy phase
healthy = sentinel.snapshot()["lanes"]["bfs"]["windows"][0]
assert healthy["n"] >= 4 and flight.incidents == 0, healthy
# commit the measured healthy window as the baseline contract
# (floor-clamped so CI scheduling hiccups sit inside the limits; the
# 0.15 s injection breaches 3x either floor with a wide margin), then
# inject: a sleeping when= hook on the serve.launch fault point — pure
# latency, no errors fire (the hook always declines the schedule)
sentinel.baseline["lanes"]["bfs"] = {
    "p50_s": max(healthy["p50_s"], 0.01),
    "p99_s": max(healthy["p99_s"], 0.02),
}
faults = global_faults()
faults.enable(seed=0)
def slow(ctx):
    time.sleep(0.15)
    return False
faults.arm("serve.launch", prob=0.0, when=slow)  # never fires, only sleeps
try:
    soak(24)  # the seeded slowdown (~3.6 s: fills both drift windows)
finally:
    faults.disarm("serve.launch")
    faults.disable()
assert flight.incidents == 1, f"want exactly 1 incident, got {flight.incidents}"
lane = sentinel.snapshot()["lanes"]["bfs"]
assert lane["violating"] is True
perf_health = runtime_health(rt)()[1]["perf"]
assert perf_health["violating"] == ["bfs"], perf_health
sentinel.close()
rt.close(); g.close()
dump, profile_dir = lane["last_incident"], lane["last_profile"]
assert dump and os.path.exists(dump), "flight window dump missing"
assert profile_dir and os.path.isdir(profile_dir), "profile dir missing"
manifest = json.load(open(os.path.join(profile_dir, "PROFILE.json")))
assert manifest["lane"] == "bfs" and "t1" in manifest, manifest
extra = [f for f in os.listdir(profile_dir) if f != "PROFILE.json"]
if manifest["profiler_active"]:
    assert extra, "active profiler session left no trace files"
import shutil
shutil.rmtree(incident_dir, ignore_errors=True)
print(f"tools/perf.sh drill: 1 incident, flight dump + profile capture "
      f"(profiler_active={manifest['profiler_active']}, "
      f"trace_files={len(extra)}) — sentinel OK")
PY
drill_rc=$?
if [ "$drill_rc" -ne 0 ]; then
    echo "tools/perf.sh: live sentinel drill failed (exit $drill_rc)" >&2
    exit "$drill_rc"
fi
echo "tools/perf.sh: perf gate green"
exit 0
