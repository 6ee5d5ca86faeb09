"""A closed loop of callers over the served lanes.

Each of ``callers`` callers sends one request, waits for its answer and
sends the next. Callers are not threads: a finished future puts its
caller back on a ready queue (``Future.add_done_callback``), and a few
submitter threads take callers off it — several, because a planned request
plans, and may be answered whole, on the thread that submits it.

Requests come in blocks of 100 whose kinds are exactly the traffic file's
mix, shuffled from the seed: every seed sends the same work in another
order. Shapes are ``chip_smoke.py``'s ``ServeGraph.requests_of`` (PR 22);
anchors are drawn as the traffic file says.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from harness import bytes_model, refs

KINDS = ("bfs", "pattern", "range", "join", "planned")


class Requests:
    """The request stream, generated block by block from the seed."""

    def __init__(self, sut, traffic: dict, seed: int, stream: int,
                 degrees: tuple):
        self.sut, self.t = sut, traffic
        self.r = np.random.default_rng([seed, 21, stream])
        self.made = {k: 0 for k in KINDS}
        self.lock = threading.Lock()
        self.buf: list = []
        cap = traffic["join"]["max_neighbourhood_degree"]
        self.deg, nbr = degrees
        self.join_ok = (self.deg <= cap) & (nbr <= cap)

    def _endpoint(self, li: int) -> int:
        s = self.sut
        return int(s.link_a[li] if self.r.integers(0, 2) else s.link_b[li])

    def _one(self, kind: str) -> dict:
        s, t, r = self.sut, self.t, self.r
        i = self.made[kind]
        self.made[kind] += 1
        n_links = len(s.link_h)
        li = int(r.integers(0, n_links))
        if kind == "bfs":
            hops = t["bfs"]["hops"]
            return {"kind": kind, "hops": hops[i % len(hops)],
                    "atoms": [self._endpoint(li)]}
        if kind == "pattern":
            a, b = int(s.link_a[li]), int(s.link_b[li])
            if i % t["pattern"]["unjoined_every"] == \
                    t["pattern"]["unjoined_every"] - 1:
                b = int(s.link_b[int(r.integers(0, n_links))])
            typed = i % t["pattern"]["typed_every"] == 0
            return {"kind": kind, "atoms": [a, b],
                    "type": s.link_type if typed else None}
        if kind == "range":
            lo = int(s.link_val[0]) + int(r.integers(0, max(n_links - 100, 1)))
            width = int(r.integers(0, t["range"]["max_width"]))
            desc = i % t["range"]["desc_every"] == t["range"]["desc_every"] - 1
            return {"kind": kind, "lo": lo, "hi": lo + width, "desc": desc,
                    "atoms": [0]}
        if kind == "join":
            while True:
                a = self._endpoint(int(r.integers(0, n_links)))
                if self.join_ok[a]:
                    return {"kind": kind, "atoms": [a]}
        windowed = i % t["planned"]["window_every"] == 0
        while windowed and self.deg[s.link_b[li]] > \
                t["planned"]["window_anchor_max_degree"]:
            li = int(r.integers(0, n_links))
        a, b, v = int(s.link_a[li]), int(s.link_b[li]), int(s.link_val[li])
        w = t["planned"]["window_half_width"]
        return {"kind": kind, "atoms": [a, b],
                "window": (v - w, v + w) if windowed else None}

    def _block(self) -> list:
        kinds = [k for k in KINDS for _ in range(self.t["mix_per_100"][k])]
        return [self._one(kinds[j]) for j in self.r.permutation(len(kinds))]

    def next(self) -> dict:
        with self.lock:
            if not self.buf:
                self.buf = self._block()[::-1]
            return self.buf.pop()

    def of_kind(self, kind: str, n: int) -> list:
        return [self._one(kind) for _ in range(n)]

    def joins_of_degree(self, degree: int, n: int) -> list:
        """Warm-up only: up to ``n`` joins anchored at that exact degree."""
        fit = np.flatnonzero(self.join_ok & (self.deg == degree))
        return [{"kind": "join", "atoms": [int(a)]}
                for a in self.r.permutation(fit)[:n]]


def degrees_of(sut) -> tuple:
    """Per atom: its degree, and the widest degree among its neighbours."""
    deg = np.bincount(np.concatenate([sut.link_a, sut.link_b]),
                      minlength=sut.e0 + sut.n_entities)
    nbr = np.zeros_like(deg)
    np.maximum.at(nbr, sut.link_a, deg[sut.link_b])
    np.maximum.at(nbr, sut.link_b, deg[sut.link_a])
    return deg, nbr


def submit(rt, q: dict):
    from hypergraphdb_tpu.query import conditions as c
    from hypergraphdb_tpu.query.variables import var

    k = q["kind"]
    if k == "bfs":
        return rt.submit_bfs(q["atoms"][0], max_hops=q["hops"])
    if k == "pattern":
        return rt.submit_pattern(q["atoms"], type_handle=q["type"])
    if k == "range":
        return rt.submit_range(q["lo"], q["hi"], desc=q["desc"])
    if k == "join":
        a = q["atoms"][0]
        return rt.submit_join({"y": c.CoIncident(a),
                               "z": c.CoIncident(var("y"))})
    a, b = q["atoms"]
    if q["window"] is None:
        return rt.submit_planned(c.And(c.Incident(a), c.Incident(b)))
    lo, hi = q["window"]
    return rt.submit_planned(c.And(c.AtomValue(lo, "gte"),
                                   c.AtomValue(hi, "lte"), c.Incident(b)))


COUNTERS = ("submitted", "completed", "shed_deadline", "rejected_queue_full",
            "errors", "host_fallbacks", "batches", "device_dispatches",
            "range_dispatches", "bfs_fused_dispatches", "retries",
            "breaker_trips")


class Driver:
    def __init__(self, sut, cfg: dict, traffic: dict, seed: int, setup: dict):
        self.sut, self.traffic, self.seed = sut, traffic, seed
        self.setup = setup
        self.top_r = sut.serve_config.top_r
        self.degrees = degrees_of(sut)

    # -- the loop ---------------------------------------------------------
    def _loop(self, requests: Requests, seconds: float | None,
              total: int | None) -> tuple:
        """Run the closed loop for ``seconds`` (or until ``total`` requests
        were sent), then wait for the stragglers; returns (one record per
        request, the loop's start, its close)."""
        rt, t = self.sut.rt, self.traffic
        ready: queue.SimpleQueue = queue.SimpleQueue()
        recs: list = []
        stop = threading.Event()
        sent = [0]
        sent_lock = threading.Lock()

        def on_done(rec, fut):
            rec["fut"] = fut
            rec["t_done"] = time.perf_counter()
            if not stop.is_set():
                ready.put((rec["caller"], rec["t_done"]))

        def submitter():
            while True:
                item = ready.get()
                if item is None:
                    return
                caller, t_free = item
                if stop.is_set():
                    continue
                if total is not None:
                    with sent_lock:
                        if sent[0] >= total:
                            continue
                        sent[0] += 1
                q = requests.next()
                rec = {"q": q, "caller": caller, "t_free": t_free,
                       "t_send": time.perf_counter(), "t_done": None,
                       "fut": None, "error": None}
                recs.append(rec)
                try:
                    fut = submit(rt, q)
                except Exception as e:  # noqa: BLE001 - counted as failed
                    rec["error"] = repr(e)
                    rec["t_done"] = time.perf_counter()
                    ready.put((caller, rec["t_done"]))
                    continue
                fut.add_done_callback(lambda f, rec=rec: on_done(rec, f))

        threads = [threading.Thread(target=submitter, name=f"submit-{i}",
                                    daemon=True)
                   for i in range(t["submit_threads"])]
        t0 = time.perf_counter()
        for c in range(t["callers"]):
            ready.put((c, t0))
        for th in threads:
            th.start()
        if seconds is not None:
            time.sleep(seconds)
        else:
            while True:
                with sent_lock:
                    if sent[0] >= total:
                        break
                time.sleep(0.05)
        stop.set()
        t_close = time.perf_counter()
        for _ in threads:
            ready.put(None)
        for th in threads:
            th.join(timeout=120)
        # stragglers: sent inside the window, answered after it. Late is
        # late, not wrong: wait for each
        deadline = t_close + t["straggler_timeout_s"]
        for rec in list(recs):
            while rec["t_done"] is None and time.perf_counter() < deadline:
                time.sleep(0.01)
        late = [r for r in recs if r["t_done"] is None]
        if late:
            self._say_where_it_hangs(late, t_close)
        return recs, t0, t_close

    def _say_where_it_hangs(self, late: list, t_close: float) -> None:
        """Requests that no answer came for: what they are, what the
        runtime counts, and where every thread of the process stands."""
        import sys
        import traceback

        kinds: dict = {}
        for r in late:
            k = r["q"]["kind"]
            kinds[k] = kinds.get(k, 0) + 1
        sent = sorted(round(r["t_send"] - t_close, 2) for r in late)
        print(f"bench: {len(late)} requests unanswered "
              f"{self.traffic['straggler_timeout_s']} s past the close: "
              f"{kinds}; sent at {sent[0]}..{sent[-1]} s of the close; "
              f"runtime: {self.sut.rt.stats_snapshot()}", file=sys.stderr)
        names = {th.ident: th.name for th in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            print(f"bench: thread {names.get(ident, ident)}:\n"
                  + "".join(traceback.format_stack(frame)[-8:]),
                  file=sys.stderr)

    def warm(self) -> None:
        """Every lane at the shapes the window can form: per kind, one
        burst (wide enough to fill the widest bucket the executor admits,
        where the traffic file says so), then a narrow batch; a join's pads
        follow the widest row among a batch's anchors, so one narrow batch
        per anchor degree up to the lane's cap; then the mix itself for a
        while."""
        rt, t = self.sut.rt, self.traffic
        warm = Requests(self.sut, t, self.seed, 0, self.degrees)

        def batch(qs):
            for f in [submit(rt, q) for q in qs]:
                f.result(timeout=900)

        for kind in KINDS:
            t0 = time.perf_counter()
            batch(warm.of_kind(kind, t["warm_burst"][kind]))
            batch(warm.of_kind(kind, 3))
            if kind == "join":
                for d in range(1, t["join"]["max_neighbourhood_degree"] + 1):
                    batch(warm.joins_of_degree(d, 3))
            self.setup[f"warm_{kind}_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self._loop(warm, None, t["warm_mixed"])
        self.setup["warm_mixed_s"] = time.perf_counter() - t0

    def run(self, seconds: float) -> dict:
        before = self.counters()
        recs, t0, t_close = self._loop(
            Requests(self.sut, self.traffic, self.seed, 1, self.degrees),
            seconds, None)
        after = self.counters()
        self.recs = recs
        window_s = t_close - t0
        timeout_ms = 1e3 * (seconds + self.traffic["straggler_timeout_s"])
        lat, by_lane, failed, in_window = [], {"bfs": [], "other": []}, 0, 0
        by_kind: dict = {}
        bfs_by_hops: dict = {}
        for rec in recs:
            ok = rec["error"] is None and rec["t_done"] is not None
            if ok:
                try:
                    rec["res"] = rec["fut"].result(timeout=0)
                except Exception as e:  # noqa: BLE001 - counted as failed
                    rec["error"], ok = repr(e), False
            rec["fut"] = None
            # a failed request counts as slower than any limit
            ms = (1e3 * (rec["t_done"] - rec["t_send"]) if ok else timeout_ms)
            failed += not ok
            lat.append(ms)
            by_lane["bfs" if rec["q"]["kind"] == "bfs" else "other"].append(ms)
            by_kind.setdefault(rec["q"]["kind"] + str(rec["q"].get("hops", "")),
                               []).append(ms)
            in_window += ok and rec["t_done"] <= t_close
            if ok and rec["q"]["kind"] == "bfs":
                # (stragglers too: a traced window holds their device time)
                h = rec["q"]["hops"]
                bfs_by_hops[h] = bfs_by_hops.get(h, 0) + 1
        lag = [1e3 * (rec["t_send"] - rec["t_free"]) for rec in recs]

        def pct(xs, p):
            return float(np.percentile(xs, p)) if xs else None

        return {
            "window_s": window_s, "attempted": len(recs), "failed": failed,
            "end_to_end": {"served_rate": in_window / window_s,
                           "served_p50_ms": pct(lat, 50),
                           "served_p95_ms": pct(lat, 95)},
            "lane_p95_ms": {k: pct(v, 95) for k, v in by_lane.items()},
            "completed_in_window": in_window,
            "latency_ms_by_kind": {
                k: {"n": len(v), "p50": pct(v, 50), "p95": pct(v, 95),
                    "max": max(v)} for k, v in sorted(by_kind.items())},
            "counters": {k: after[k] - before[k] for k in after},
            "bfs_bytes": bytes_model.served_bfs_bytes(
                requests_by_hops=bfs_by_hops, **self.sut.shapes),
            # how the generator itself kept up: a free caller's wait for a
            # submitter thread
            "generator": {"lag_p50_ms": pct(lag, 50),
                          "lag_p95_ms": pct(lag, 95),
                          "lag_max_ms": max(lag) if lag else None,
                          "stragglers_wait_s": max(
                              [r["t_done"] - t_close for r in recs
                               if r["t_done"] is not None] + [0.0])},
        }

    def counters(self) -> dict:
        rt = self.sut.rt
        snap = rt.stats_snapshot()
        out = {k: snap[k] for k in COUNTERS}
        for name in ("lanes_real", "lanes_padded"):
            out[name] = rt.stats.registry.get(f"serve.{name}").value
        return out

    # -- the comparison ---------------------------------------------------
    def collect(self) -> dict:
        """The answers to hold to the reference — every request of the
        short lanes and a sample of the BFS requests with both hop counts,
        drawn from the seed — as plain rows; then the program's state goes."""
        r = np.random.default_rng([self.seed, 22])
        bfs = [i for i, rec in enumerate(self.recs)
               if rec["q"]["kind"] == "bfs"]
        keep = set(r.permutation(bfs)[: self.traffic["check_bfs_sample"]]
                   .tolist())
        got = []
        for i, rec in enumerate(self.recs):
            q, res = rec["q"], rec.get("res")
            if q["kind"] == "bfs" and i not in keep:
                continue
            if res is None:
                got.append({"q": q, "missing": True})
                continue
            rows = res.tuples if q["kind"] == "join" else res.matches
            got.append({"q": q, "missing": False, "count": int(res.count),
                        "rows": np.asarray(rows).copy(),
                        "truncated": bool(res.truncated),
                        "host": getattr(res, "served_by", "") == "host"})
        self.sut_arrays = (self.sut.link_h, self.sut.link_a, self.sut.link_b,
                           self.sut.link_val, self.sut.link_type)
        self.recs = None
        t0 = time.perf_counter()
        self.sut.close()
        self.setup["close_s"] = time.perf_counter() - t0
        return {"answers": got}

    def reference(self, qs: list, stale_links: int = 0) -> list:
        """The reference's answer to each request; ``stale_links`` makes it
        the control (see ``refs.ServeReference``)."""
        ref = refs.ServeReference(*self.sut_arrays, stale_links=stale_links)
        out: list = [None] * len(qs)
        by_hops: dict = {}
        for i, q in enumerate(qs):
            if q["kind"] == "bfs":
                by_hops.setdefault(q["hops"], []).append(i)
            else:
                out[i] = ref.answer(q)
        for hops, idx in by_hops.items():
            for i, vis in zip(idx, ref.bfs_many(
                    [qs[i]["atoms"][0] for i in idx], hops)):
                out[i] = vis
        return out

    def check(self, got: dict) -> dict:
        """Every number compared, beside its limit: all exact."""
        answers = got["answers"]
        want = self.reference([a["q"] for a in answers])
        missing = sum(a["missing"] for a in answers)
        wrong = sum(
            not a["missing"] and not refs.answer_matches(
                a["q"], w, a["count"], a["rows"], a["truncated"], self.top_r)
            for a, w in zip(answers, want))
        return {"answers_wrong": (wrong, 0), "answers_missing": (missing, 0),
                "answers_compared": (len(answers), None)}

    def control(self, got: dict) -> dict:
        """The comparison of the CONTROL's answers: the reference in the
        program's place with one stated guarantee broken — a stale reader,
        to which the newest ``control_stale_links`` acknowledged links (the
        last ``bulk_import``) are invisible. It has to come out as not
        correct."""
        qs = [a["q"] for a in got["answers"]]
        answers = []
        for q, w in zip(qs, self.reference(
                qs, stale_links=self.traffic["control_stale_links"])):
            rows = w if q["kind"] == "planned" else w[: self.top_r]
            answers.append({"q": q, "missing": False, "count": len(w),
                            "rows": rows, "truncated": len(w) > len(rows)})
        return self.check({"answers": answers})
