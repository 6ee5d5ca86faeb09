#!/usr/bin/env bash
# hgreplica gate: the replicated-serving-tier suite — replica node
# lifecycle (bootstrap→follow→serve, the lag gate), front-door placement
# + breaker failover, gap-aware replication convergence (contiguity
# tracking, anti-entropy, the redelivery journal), and the chunk-boundary
# crash recovery drill — followed by a LIVE smoke: a primary + 2 serving
# replicas + the front door over real HTTP sockets, one replica killed
# mid-scrape, and every submit through the door must come back 200
# (curl -f when present, stdlib urllib otherwise — degraded, never down).
#
# Sits beside lint.sh (AST hazards), verify.sh (jaxpr ground truth),
# chaos.sh (fault injection), obs.sh (telemetry), and perf.sh (Pallas
# gather + AOT): this one gates the deployment tier.
#
# Usage: tools/replica.sh [extra pytest args]
#   tools/replica.sh -k router         # one area, fast local run
set -uo pipefail
cd "$(dirname "$0")/.."

JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
    tests/test_replication_gaps.py \
    tests/test_replica.py \
    tests/test_replica_router.py \
    tests/test_replica_recovery.py \
    -q -m 'not slow' -p no:cacheprovider "$@"
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "tools/replica.sh: replica tests failed (exit $rc)" >&2
    exit "$rc"
fi

# -- live smoke: the whole tier over real sockets ----------------------------
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'PY'
import json
import shutil
import subprocess
import urllib.request

import hypergraphdb_tpu as hg
from hypergraphdb_tpu.obs.http import runtime_health
from hypergraphdb_tpu.peer import transfer
from hypergraphdb_tpu.peer.peer import HyperGraphPeer
from hypergraphdb_tpu.peer.transport import LoopbackNetwork
from hypergraphdb_tpu.replica import (
    FrontDoor,
    HTTPBackend,
    ReplicaConfig,
    ReplicaNode,
    RouterConfig,
    SubmitServer,
    frontdoor_server,
    node_server,
    submit_payload,
)
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime


def serve_cfg():
    return ServeConfig(max_linger_s=0.001, top_r=8, prewarm_aot=False)


net = LoopbackNetwork()
gp = hg.HyperGraph()
pp = HyperGraphPeer.loopback(gp, net, identity="primary")
pp.replication.debounce_s = 0.005
pp.start()
hs = [int(gp.add(f"s{i}")) for i in range(10)]
for i in range(9):
    gp.add_link([hs[i], hs[i + 1]], value=f"e{i}")


def replica(ident):
    gr = hg.HyperGraph()
    node = ReplicaNode(
        gr, HyperGraphPeer.loopback(gr, net, identity=ident),
        ReplicaConfig(primary="primary", serve=serve_cfg()),
    )
    node.start()
    assert node.wait_converged(timeout=60), f"{ident} never converged"
    return node


n1, n2 = replica("r1"), replica("r2")
prt = ServeRuntime(gp, serve_cfg())
s1, s2 = node_server(n1).start(), node_server(n2).start()
sp = SubmitServer(lambda p: submit_payload(prt, p, 30.0),
                  health=runtime_health(prt)).start()
fd = FrontDoor(
    HTTPBackend("primary", sp.url, role="primary"),
    [HTTPBackend("r1", s1.url), HTTPBackend("r2", s2.url)],
    RouterConfig(breaker_threshold=2, breaker_cooldown_s=5.0,
                 poll_interval_s=0.1),
).start()
fsrv = frontdoor_server(fd).start()

gid = transfer.gid_of(gp, hs[0], "primary")
body = json.dumps({"kind": "bfs", "seed_gid": gid, "max_hops": 2,
                   "deadline_s": 10.0})
curl = shutil.which("curl")


def post():
    """One submit through the front door; raises on any non-200."""
    url = fsrv.url + "/submit"
    if curl:
        out = subprocess.run(
            [curl, "-fsS", "--max-time", "15",
             "-H", "Content-Type: application/json", "-d", body, url],
            check=True, capture_output=True, text=True,
        )
        return json.loads(out.stdout)
    req = urllib.request.Request(
        url, data=body.encode("utf-8"),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=15) as r:
        assert r.status == 200
        return json.loads(r.read().decode("utf-8"))


def get_healthz():
    url = fsrv.url + "/healthz"
    if curl:
        out = subprocess.run([curl, "-fsS", "--max-time", "10", url],
                             check=True, capture_output=True, text=True)
        return json.loads(out.stdout)
    with urllib.request.urlopen(url, timeout=10) as r:
        assert r.status == 200
        return json.loads(r.read().decode("utf-8"))


try:
    routed = []
    for _ in range(6):                      # healthy tier: reads spread
        routed.append(post()["routed_to"])
    assert set(routed) <= {"r1", "r2"}, routed
    # KILL r2 mid-scrape (server and node, no drain — a death)
    s2.stop()
    n2.stop(drain=False)
    for _ in range(8):                      # every one still 200
        routed.append(post()["routed_to"])
    assert "r2" not in routed[6:], routed
    assert set(routed[6:]) <= {"r1", "primary"}, routed
    health = get_healthz()                  # the door itself stays 200
    assert health["role"] == "router" and "backends" in health, health
    print(f"tools/replica.sh smoke: {len(routed)} submits through "
          f"{fsrv.url} all 200 ({'curl' if curl else 'urllib'}); "
          f"r2 killed mid-scrape, routed_to={routed}")
finally:
    fsrv.stop()
    fd.stop()
    sp.stop()
    s1.stop()
    prt.close()
    n1.stop()
    pp.stop()
    gp.close()
PY
smoke_rc=$?
if [ "$smoke_rc" -ne 0 ]; then
    echo "tools/replica.sh: live failover smoke failed (exit $smoke_rc)" >&2
    exit "$smoke_rc"
fi
echo "tools/replica.sh: replica gate green"
exit 0
