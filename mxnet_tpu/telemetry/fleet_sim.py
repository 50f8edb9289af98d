"""Fleet-scale observability simulator (ISSUE 20 tentpole).

``python -m mxnet_tpu.telemetry.fleet_sim --ranks 1000`` runs N
synthetic fleet reporters — each with its own seeded metric-family
generator (cardinality drawn from the REAL registry's family catalog,
plus scripted anomalies: a rank going silent, a burn-rate breach, a
numerics page) — against ONE real leader: a real
:class:`~mxnet_tpu.kvstore_server.KVServer` (virtual clock injected),
its real :class:`~mxnet_tpu.telemetry.fleet.FleetStore` merge path
(``KVServer.apply_telemetry_push`` — the exact ``telemetry_push`` op
body), the real :func:`~mxnet_tpu.telemetry.fleet.merge_server`
rollup, and a real :class:`~mxnet_tpu.telemetry.alerts.AlertEngine`
judging the fleet through the registered provider.  Everything runs
in-process with virtualized time, so a 1000-rank, 50-push-cycle run
completes in seconds on a laptop.

The report is machine-readable (``--json``) and the simulator IS the
gate (the CI smoke calls it):

* ``merge_p99_ms``  — per-push leader merge cost, p99 < 1 ms;
* ``rollup_ms``     — summary rollup at scrape, max < 50 ms;
* ``scrape_kib``    — summary ``/fleet.json`` bytes, < 256 KiB;
* ``alert lag``     — injected breach -> leader-visible firing,
  < 2 push intervals;
* ``sublinearity``  — rank=1000 merge p99 ≤ 3× rank=100 (a reference
  run at rank=100 precedes the main run);
* plus the back-compat pin: at rank ≤ 8 the delta-pushed store renders
  a ``detail="rank"`` view byte-identical to the pre-ISSUE-20 merge
  path fed the same pushes in full (a shadow legacy store).

Allocation behavior is sampled with :mod:`tracemalloc` over a mid-run
window (docs/observability.md "fleet at scale" runbook).
"""
from __future__ import annotations

import argparse
import gc
import json
import pickle
import random
import sys
import time
import tracemalloc


def _percentile(vals, q):
    if not vals:
        return 0.0
    s = sorted(vals)
    idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[idx]


class SimClock:
    """Virtual monotonic clock: the KVServer, FleetStore and
    AlertEngine all read it, so peer timeouts, snapshot ages and alert
    ``for``-durations mature at simulated push-interval speed."""

    def __init__(self, start=1000.0):
        self.now = float(start)

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += float(dt)


# -- synthetic ranks ----------------------------------------------------------
# synthetic families layered ON TOP of whatever the real registry
# already exposes in this process — together they give each rank a
# catalog with realistic cardinality (histogram sample families with le
# labels, label-spread gauges, hot counters, cold config gauges)
_SYNTH_FAMILIES = (
    ("mxnet_sim_step_total", "counter", ({},), True),
    ("mxnet_sim_loss", "gauge", ({},), True),
    ("mxnet_sim_collective_bytes_total", "counter",
     tuple({"op": op} for op in ("push", "pull", "allreduce",
                                 "broadcast")), True),
    ("mxnet_sim_step_seconds_bucket", "counter",
     tuple({"le": le} for le in ("0.01", "0.05", "0.1", "0.5", "1.0",
                                 "+Inf")), True),
    ("mxnet_sim_step_seconds_sum", "counter", ({},), True),
    ("mxnet_sim_step_seconds_count", "counter", ({},), True),
    ("mxnet_sim_queue_depth", "gauge",
     tuple({"lane": str(i)} for i in range(8)), True),
    ("mxnet_sim_device_mem_bytes", "gauge",
     tuple({"device": str(i)} for i in range(4)), False),
    ("mxnet_sim_config_info", "gauge", ({},), False),
    ("mxnet_serving_requests_total", "counter", ({},), True),
    ("mxnet_serving_shed_total", "counter", ({},), True),
    ("mxnet_numerics_nonfinite_windows_total", "counter", ({},), False),
)


def _base_catalog():
    """(family, type, label_sets, hot) rows: the process's REAL
    registry catalog (cold — real families barely move between pushes)
    plus the synthetic hot set above."""
    from . import REGISTRY
    rows = []
    for name, fam in sorted(REGISTRY.sample_families().items()):
        labels = tuple(dict(s.get("labels", {}))
                       for s in fam.get("values", [])[:16])
        if labels:
            rows.append((name, fam.get("type", "gauge"), labels, False))
    rows.extend(_SYNTH_FAMILIES)
    return rows


class SimRank:
    """One synthetic fleet reporter: seeded per-family value streams,
    a real :class:`~.registry.SampleDeltaEncoder`, and scripted
    anomaly hooks (silence / burn-rate breach / numerics page) whose
    ``mxnet_alert_state`` one-hot gauges ride the push exactly like a
    real rank's alert engine output."""

    def __init__(self, rank, seed, catalog, clock, delta=True):
        self.rank = int(rank)
        self.rng = random.Random((int(seed) * 1000003) ^ (rank + 1))
        self._clock = clock
        self.catalog = catalog
        self.silent = False
        self.joined = True
        self.alert_states = {}          # rule -> state (one-hot)
        self._fams = {}                 # family -> current family dict
        self._vals = {}                 # (family, idx) -> value
        if delta:
            from .registry import SampleDeltaEncoder
            self.encoder = SampleDeltaEncoder()
        else:
            self.encoder = None
        for name, mtype, label_sets, _hot in catalog:
            for i in range(len(label_sets)):
                self._vals[(name, i)] = (
                    self.rng.uniform(0, 100) if mtype == "gauge"
                    else float(self.rng.randrange(1000)))
            self._rebuild(name)

    def _rebuild(self, name):
        """Fresh family dict (never mutate in place: the delta encoder
        keeps the previous object as its acked baseline)."""
        for fname, mtype, label_sets, _hot in self.catalog:
            if fname != name:
                continue
            self._fams[name] = {
                "type": mtype,
                "values": [{"labels": dict(ls),
                            "value": self._vals[(name, i)]}
                           for i, ls in enumerate(label_sets)]}
            return

    def step(self):
        """Advance one push interval: hot families move every cycle,
        cold families occasionally — a realistic delta footprint."""
        for name, mtype, label_sets, hot in self.catalog:
            if not hot and self.rng.random() > 0.02:
                continue
            for i in range(len(label_sets)):
                key = (name, i)
                if mtype == "counter":
                    self._vals[key] += self.rng.uniform(0, 50)
                else:
                    self._vals[key] += self.rng.uniform(-1, 1)
            self._rebuild(name)

    def breach_burn_rate(self):
        """Scripted SLO breach: sheds ramp hard and this rank's alert
        engine (simulated output) flips shed_burn_rate to firing."""
        for i in range(1):
            self._vals[("mxnet_serving_shed_total", i)] += 5000.0
        self._rebuild("mxnet_serving_shed_total")
        self.alert_states["shed_burn_rate"] = "firing"
        self._rebuild_alerts()

    def page_numerics(self):
        """Scripted numerics page: a non-finite window lands."""
        self._vals[("mxnet_numerics_nonfinite_windows_total", 0)] += 1.0
        self._rebuild("mxnet_numerics_nonfinite_windows_total")
        self.alert_states["nonfinite_window"] = "firing"
        self._rebuild_alerts()

    def _rebuild_alerts(self):
        values = []
        for rule, state in self.alert_states.items():
            for s in ("pending", "firing", "resolved", "inactive"):
                values.append({"labels": {"rule": rule, "state": s},
                               "value": 1 if s == state else 0})
        self._fams["mxnet_alert_state"] = {"type": "gauge",
                                           "values": values}

    def payload(self):
        full = {"time": self._clock(), "families": dict(self._fams)}
        if self.encoder is None:
            return full
        return self.encoder.encode(full)

    def full_families(self):
        return dict(self._fams)


# -- the simulation -----------------------------------------------------------
def _make_leader(ranks, interval_s, clock):
    from ..kvstore_server import KVServer
    return KVServer(port=0, num_workers=int(ranks),
                    peer_timeout_s=float(interval_s) * 2.5, clock=clock)


def _heartbeat(server, rank, step, clock):
    # the heartbeat op body (kvstore_server._handle), sans socket
    with server._lock:
        server._heartbeats[int(rank)] = clock()
        server._progress[int(rank)] = int(step)


def run_sim(ranks=1000, cycles=50, interval_s=5.0, seed=0, delta=True,
            churn=None, alloc_window=5, verbose=False):
    """One simulated fleet run; returns the raw stats dict.

    ``churn``: optional ``{"die": [rank...], "die_at": cycle,
    "join": [rank...], "join_at": cycle}`` — joining ranks stay silent
    (state ``unknown``) until ``join_at``; dying ranks stop pushing and
    heartbeating at ``die_at`` and must age to ``lost``.
    """
    from . import fleet
    from .alerts import AlertEngine, default_rules
    from ..chaos.failpoints import failpoint as _failpoint, \
        ChaosInjectedError

    clock = SimClock()
    server = _make_leader(ranks, interval_s, clock)
    catalog = _base_catalog()
    sims = [SimRank(r, seed, catalog, clock, delta=delta)
            for r in range(int(ranks))]
    # The simulator hosts ALL N ranks' object graphs in one process — a
    # topology no real leader has.  Automatic gen-2 GC passes scan those
    # millions of synthetic fixture objects (~100 ms each at rank=1000)
    # and the pause lands inside whichever leader call happens to be
    # running, polluting the merge/rollup gates with pure simulator
    # overhead.  The per-cycle family churn is acyclic (plain dicts and
    # lists), so refcounting reclaims it; defer cycle collection to
    # teardown and keep the measured window collection-free.
    gc.collect()
    gc.freeze()
    gc_was_enabled = gc.isenabled()
    gc.disable()

    churn = churn or {}
    die_set = set(churn.get("die", ()))
    join_set = set(churn.get("join", ()))
    die_at = int(churn.get("die_at", -1))
    join_at = int(churn.get("join_at", 0))
    for s in sims:
        if s.rank in join_set:
            s.joined = False

    # scripted anomalies (skipped for ranks the churn plan controls)
    silent_rank = next((r for r in (7 % ranks, 5 % ranks)
                        if r not in die_set | join_set), 0)
    breach_rank = next((r for r in (11 % ranks, 3 % ranks)
                        if r not in die_set | join_set
                        and r != silent_rank), 1 % ranks)
    numerics_rank = next((r for r in (13 % ranks, 2 % ranks)
                          if r not in die_set | join_set
                          and r not in (silent_rank, breach_rank)),
                         0)
    silent_cycle = max(2, cycles // 2)
    breach_cycle = max(1, cycles // 3)
    numerics_cycle = max(1, (2 * cycles) // 3)

    old_provider = fleet.provider()
    fleet.set_provider(
        lambda detail=None: fleet.merge_server(server, detail=detail,
                                               _now=clock()))
    engine = AlertEngine(rules=default_rules())

    merge_s = []
    rollup_s = []
    scrape_bytes = 0
    wire = {"full": 0, "delta": 0}
    pushes = {"full": 0, "delta": 0, "resync": 0, "dropped": 0}
    leader_exceptions = []
    breach_visible_cycle = None
    alloc = {"bytes_per_cycle": None, "count_per_cycle": None}
    alloc_started = False
    alloc_t0 = None
    summary = {}

    def _push(sim):
        payload = sim.payload()
        try:
            _failpoint("fleet/push")
        except ChaosInjectedError:
            pushes["dropped"] += 1
            return
        mode = "delta" if "delta" in payload else "full"
        wire[mode] += len(pickle.dumps(
            payload, protocol=pickle.HIGHEST_PROTOCOL))
        t0 = time.perf_counter()
        try:
            resp = server.apply_telemetry_push(sim.rank, payload)
        except Exception as e:  # noqa: BLE001 — a leader exception is itself a gated failure, record it
            leader_exceptions.append(f"{type(e).__name__}: {e}")
            return
        merge_s.append(time.perf_counter() - t0)
        if resp.get("resync") and sim.encoder is not None:
            pushes["resync"] += 1
            sim.encoder.reset()
            payload = sim.payload()
            wire["full"] += len(pickle.dumps(
                payload, protocol=pickle.HIGHEST_PROTOCOL))
            t0 = time.perf_counter()
            try:
                resp = server.apply_telemetry_push(sim.rank, payload)
            except Exception as e:  # noqa: BLE001 — see above
                leader_exceptions.append(f"{type(e).__name__}: {e}")
                return
            merge_s.append(time.perf_counter() - t0)
            pushes["full"] += 1
        else:
            pushes[mode] += 1
        if sim.encoder is not None and resp.get("acked") is not None:
            sim.encoder.ack(resp["acked"])

    try:
        for cycle in range(int(cycles)):
            clock.advance(interval_s)
            if alloc_window and cycle == cycles // 2:
                tracemalloc.start()
                alloc_t0 = tracemalloc.take_snapshot()
                alloc_started = True
            if cycle == silent_cycle:
                sims[silent_rank].silent = True
            if cycle == breach_cycle:
                sims[breach_rank].breach_burn_rate()
            if cycle == numerics_cycle:
                sims[numerics_rank].page_numerics()
            if die_at >= 0 and cycle == die_at:
                for s in sims:
                    if s.rank in die_set:
                        s.silent = True
            if cycle == join_at:
                for s in sims:
                    if s.rank in join_set:
                        s.joined = True
            for sim in sims:
                if sim.silent or not sim.joined:
                    continue
                sim.step()
                _heartbeat(server, sim.rank, cycle, clock)
                _push(sim)
            # leader scrape: the summary rollup + the real AlertEngine
            t0 = time.perf_counter()
            try:
                summary = fleet.merge_server(server, detail="summary",
                                             _now=clock())
            except Exception as e:  # noqa: BLE001 — a rollup exception is a gated failure
                leader_exceptions.append(f"{type(e).__name__}: {e}")
                summary = {}
            rollup_s.append(time.perf_counter() - t0)
            scrape_bytes = len(json.dumps(summary, default=str,
                                          sort_keys=True))
            engine.tick(now=clock())
            if breach_visible_cycle is None:
                for f in (summary.get("alerts") or {}).get("firing", ()):
                    if f.get("rank") == str(breach_rank) and \
                            f.get("rule") == "shed_burn_rate":
                        breach_visible_cycle = cycle
                        break
            if alloc_started and cycle == cycles // 2 + alloc_window - 1:
                diff = tracemalloc.take_snapshot().compare_to(
                    alloc_t0, "filename")
                tracemalloc.stop()
                alloc_started = False
                alloc["bytes_per_cycle"] = int(
                    sum(d.size_diff for d in diff) / alloc_window)
                alloc["count_per_cycle"] = int(
                    sum(d.count_diff for d in diff) / alloc_window)
            if verbose and cycle % 10 == 0:
                print(f"[fleet_sim] cycle {cycle}/{cycles} "
                      f"merge_p99={_percentile(merge_s, 0.99)*1e3:.3f}ms",
                      flush=True)
    finally:
        if alloc_started:
            tracemalloc.stop()
        gc.unfreeze()
        if gc_was_enabled:
            gc.enable()
        gc.collect()
        fleet.set_provider(old_provider)

    states = server._peer_states()
    return {
        "ranks": int(ranks), "cycles": int(cycles),
        "interval_s": float(interval_s), "seed": int(seed),
        "delta": bool(delta),
        "merge": {
            "pushes": len(merge_s),
            "p50_ms": _percentile(merge_s, 0.5) * 1e3,
            "p99_ms": _percentile(merge_s, 0.99) * 1e3,
            "max_ms": (max(merge_s) * 1e3) if merge_s else 0.0,
            "full": pushes["full"], "delta": pushes["delta"],
            "resync": pushes["resync"], "dropped": pushes["dropped"]},
        "push_bytes": {
            "full_total": wire["full"], "delta_total": wire["delta"],
            "delta_mean": (wire["delta"] / max(1, pushes["delta"])),
            "full_mean": (wire["full"] / max(1, pushes["full"]))},
        "rollup": {
            "p50_ms": _percentile(rollup_s, 0.5) * 1e3,
            "max_ms": (max(rollup_s) * 1e3) if rollup_s else 0.0},
        "scrape": {"summary_bytes": scrape_bytes,
                   "summary_kib": scrape_bytes / 1024.0},
        "alloc": alloc,
        "alerts": {
            "breach_rank": breach_rank,
            "breach_cycle": breach_cycle,
            "visible_cycle": breach_visible_cycle,
            "lag_intervals": (None if breach_visible_cycle is None
                              else breach_visible_cycle - breach_cycle),
            "leader_firing": sorted(
                a["rule"] for a in
                (summary.get("alerts") or {}).get("firing", ())),
            "silent_rank": silent_rank,
            "silent_rank_state": states.get(silent_rank, {}).get(
                "state"),
            "numerics_rank": numerics_rank},
        "leader_exceptions": leader_exceptions,
        "final_summary": {
            "peers": summary.get("peers"),
            "anomalous": sorted((summary.get("anomalous") or {})),
            "push_stats": summary.get("push_stats")},
    }


# -- back-compat pin ----------------------------------------------------------
def run_backcompat(ranks=8, cycles=6, interval_s=5.0, seed=0):
    """Delta-pushed store vs a shadow pre-ISSUE-20 store fed the SAME
    pushes in full, rendered through the same merge algorithm — the
    detail ``/fleet.json`` must be byte-identical at rank ≤ 8.
    Includes a generation bump mid-run (resync + history) and a silent
    rank (lost/stale tagging on both sides)."""
    from . import fleet

    clock = SimClock()
    server = _make_leader(ranks, interval_s, clock)
    catalog = _base_catalog()
    sims = [SimRank(r, seed, catalog, clock, delta=True)
            for r in range(int(ranks))]
    shadow = {}   # the legacy {gen: {rank: {"payload", "mono"}}} store
    silent_rank = ranks - 1
    resyncs = 0
    for cycle in range(int(cycles)):
        clock.advance(interval_s)
        if cycle == cycles // 2:
            server.reset_world(ranks, generation=1)
        if cycle == cycles - 2:
            sims[silent_rank].silent = True
        if cycle == 1:
            sims[0].breach_burn_rate()   # exercise the alert rollup
        with server._lock:
            gen = server._generation
        for sim in sims:
            if sim.silent:
                continue
            sim.step()
            _heartbeat(server, sim.rank, cycle, clock)
            payload = sim.payload()
            resp = server.apply_telemetry_push(sim.rank, payload)
            if resp.get("resync"):
                resyncs += 1
                sim.encoder.reset()
                resp = server.apply_telemetry_push(sim.rank,
                                                   sim.payload())
            if resp.get("acked") is not None:
                sim.encoder.ack(resp["acked"])
            shadow.setdefault(gen, {})[sim.rank] = {
                "payload": {"time": clock(),
                            "families": sim.full_families()},
                "mono": clock()}
    now_wall = clock()
    new_view = fleet.merge_server(server, detail="rank", _now=now_wall)
    with server._lock:
        gen = server._generation
        world = server.num_workers
    old_view = fleet._merge_view(
        server._peer_states(), gen, world, shadow,
        server._peer_timeout(), clock(), now_wall)
    new_json = json.dumps(new_view, default=str, sort_keys=True)
    old_json = json.dumps(old_view, default=str, sort_keys=True)
    return {"ranks": int(ranks), "cycles": int(cycles),
            "resyncs": resyncs,
            "identical": new_json == old_json,
            "new_bytes": len(new_json), "old_bytes": len(old_json)}


# -- gates + CLI --------------------------------------------------------------
GATE_MERGE_P99_MS = 1.0
GATE_ROLLUP_MS = 50.0
GATE_SCRAPE_KIB = 256.0
GATE_ALERT_LAG = 2
GATE_SUBLINEAR_FACTOR = 3.0


def evaluate(result, reference=None, backcompat=None):
    """The five ISSUE 20 gates (+ the back-compat pin) over a run."""
    lag = result["alerts"]["lag_intervals"]
    gates = {
        "merge_p99_ms": {
            "value": result["merge"]["p99_ms"],
            "limit": GATE_MERGE_P99_MS,
            "ok": result["merge"]["p99_ms"] < GATE_MERGE_P99_MS},
        "rollup_ms": {
            "value": result["rollup"]["max_ms"],
            "limit": GATE_ROLLUP_MS,
            "ok": result["rollup"]["max_ms"] < GATE_ROLLUP_MS},
        "scrape_kib": {
            "value": result["scrape"]["summary_kib"],
            "limit": GATE_SCRAPE_KIB,
            "ok": result["scrape"]["summary_kib"] < GATE_SCRAPE_KIB},
        "alert_lag_intervals": {
            "value": lag, "limit": GATE_ALERT_LAG,
            "ok": lag is not None and lag < GATE_ALERT_LAG},
        "leader_exceptions": {
            "value": len(result["leader_exceptions"]), "limit": 0,
            "ok": not result["leader_exceptions"]},
    }
    if reference is not None:
        ref_p99 = max(1e-6, reference["merge"]["p99_ms"])
        ratio = result["merge"]["p99_ms"] / ref_p99
        gates["sublinear_vs_ref"] = {
            "value": ratio, "limit": GATE_SUBLINEAR_FACTOR,
            "ref_ranks": reference["ranks"],
            "ref_p99_ms": reference["merge"]["p99_ms"],
            "ok": ratio <= GATE_SUBLINEAR_FACTOR}
    if backcompat is not None:
        gates["backcompat_identical"] = {
            "value": backcompat["identical"], "limit": True,
            "ok": bool(backcompat["identical"])}
    return gates


def main(argv=None):
    from ..config import get as _cfg
    ap = argparse.ArgumentParser(
        description="in-process fleet-scale observability simulator "
                    "(ISSUE 20; docs/observability.md 'fleet at scale')")
    ap.add_argument("--ranks", type=int,
                    default=int(_cfg("MXNET_FLEET_SIM_RANKS")))
    ap.add_argument("--cycles", type=int,
                    default=int(_cfg("MXNET_FLEET_SIM_CYCLES")))
    ap.add_argument("--interval", type=float, default=5.0,
                    help="virtual push interval seconds")
    ap.add_argument("--seed", type=int,
                    default=int(_cfg("MXNET_FLEET_SIM_SEED")))
    ap.add_argument("--no-delta", action="store_true",
                    help="force full-snapshot pushes (A/B the plane)")
    ap.add_argument("--reference-ranks", type=int, default=100,
                    help="sublinearity reference run size (0 skips)")
    ap.add_argument("--json", action="store_true",
                    help="print ONLY the machine-readable report")
    args = ap.parse_args(argv)

    delta = not args.no_delta
    t_start = time.perf_counter()
    backcompat = run_backcompat(ranks=min(8, max(2, args.ranks)),
                                seed=args.seed)
    reference = None
    if args.reference_ranks and args.ranks > args.reference_ranks:
        reference = run_sim(ranks=args.reference_ranks,
                            cycles=args.cycles,
                            interval_s=args.interval, seed=args.seed,
                            delta=delta)
    result = run_sim(ranks=args.ranks, cycles=args.cycles,
                     interval_s=args.interval, seed=args.seed,
                     delta=delta, verbose=not args.json)
    gates = evaluate(result, reference=reference, backcompat=backcompat)
    ok = all(g["ok"] for g in gates.values())
    report = {"result": result, "reference": reference,
              "backcompat": backcompat, "gates": gates, "ok": ok,
              "wall_s": time.perf_counter() - t_start}
    if args.json:
        print(json.dumps(report, default=str, sort_keys=True))
    else:
        m, r, s = result["merge"], result["rollup"], result["scrape"]
        print(f"[fleet_sim] ranks={args.ranks} cycles={args.cycles} "
              f"delta={delta} wall={report['wall_s']:.1f}s")
        print(f"[fleet_sim] merge: pushes={m['pushes']} "
              f"p50={m['p50_ms']:.3f}ms p99={m['p99_ms']:.3f}ms "
              f"full={m['full']} delta={m['delta']} "
              f"resync={m['resync']}")
        print(f"[fleet_sim] rollup: p50={r['p50_ms']:.2f}ms "
              f"max={r['max_ms']:.2f}ms  scrape={s['summary_kib']:.1f}"
              f"KiB  alloc/cycle={result['alloc']['bytes_per_cycle']}B")
        print(f"[fleet_sim] push bytes: full_mean="
              f"{result['push_bytes']['full_mean']:.0f} delta_mean="
              f"{result['push_bytes']['delta_mean']:.0f}")
        print(f"[fleet_sim] alerts: lag="
              f"{result['alerts']['lag_intervals']} intervals "
              f"silent rank {result['alerts']['silent_rank']} -> "
              f"{result['alerts']['silent_rank_state']}")
        for name, g in gates.items():
            print(f"[fleet_sim] gate {name}: value={g['value']} "
                  f"limit={g['limit']} -> "
                  f"{'OK' if g['ok'] else 'FAIL'}")
        print(f"FLEET SIM {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
