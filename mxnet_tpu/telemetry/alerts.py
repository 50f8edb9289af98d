"""In-process SLO alert engine (ISSUE 13 tentpole, half two).

PR 12 ended with an alarm *table in the docs* — a human had to read the
scrape and decide.  This module turns that table into machine-readable
judgments evaluated inside the process: declarative rules over registry
samples, a pending → firing → resolved lifecycle with for-duration
hysteresis and per-rule cooldown, and a default rule pack codifying the
documented alarms (watchdog stall, corrupt checkpoint, spill storm,
shed burn rate, retrace ratchet, RSS leak slope, fleet snapshot
staleness).  Every transition lands in the flight ring and the
``mxnet_alert_*`` families; firing **page**-severity alerts flip
``/healthz`` to 503 and the new ``GET /alerts.json`` exporter route
serves the full state — the judgment layer the ROADMAP item-4
autoscaler actuates against, and the signal the chaos soak harness
(``python -m mxnet_tpu.chaos.soak``) gates CI on.

Rule kinds:

* **threshold** — reduced family value compared against a bound
  (``mxnet_watchdog_stalled_sections > 0``);
* **rate** — change per second over a lookback window
  (``mxnet_serving_router_spill_total`` rising faster than N/s);
* **absence** — a family that should always have samples has none
  (a reporter that should be pushing went silent);
* **burn_rate** — multi-window SLO burn: the bad/total ratio over a
  *fast* and a *slow* window must BOTH exceed ``factor`` × the error
  budget (``objective``) — the standard two-window burn-rate alarm, so
  a single shed blip neither pages (fast-only) nor does a slow leak
  hide (slow-only).  docs/observability.md has the math.

Lifecycle: a true condition moves a rule to ``pending``; held for
``for_s`` seconds it escalates to ``firing``; a false condition from
``firing`` moves to ``resolved``, which decays to ``inactive`` after
``cooldown_s`` — and re-firing is suppressed until the cooldown
expires, so a flapping signal cannot page in a loop.

Rank-local engines export their state as registry gauges
(``mxnet_alert_state{rule,state}``), which ride the PR-12 fleet push —
the leader's ``/fleet.json`` carries a fleet-wide alert rollup with
lost ranks' stale alerts tagged.

``MXNET_ALERTS=<seconds>`` arms a daemon evaluation thread at that
interval; the disabled module-level :func:`tick` is one global check
(< 1 µs, like a disabled span, trace hook or failpoint).
"""
from __future__ import annotations

import collections
import logging
import sys
import threading
import time

from ..base import MXNetError

log = logging.getLogger("mxnet_tpu.telemetry.alerts")

SEVERITIES = ("warn", "page")
KINDS = ("threshold", "rate", "absence", "burn_rate")
STATES = ("inactive", "pending", "firing", "resolved")

# module-global fast gate: the ONLY thing a disabled tick() touches
_armed = False

_lock = threading.Lock()
_engine = None
_thread = None
_stop = None


class AlertRule:
    """One declarative rule over registry samples."""

    def __init__(self, name, family, kind="threshold", op=">", value=0.0,
                 for_s=0.0, cooldown_s=30.0, severity="warn",
                 reduce="sum", labels=None, window_s=60.0,
                 total_family=None, objective=0.05, factor=2.0,
                 fast_s=60.0, slow_s=300.0, doc=""):
        if kind not in KINDS:
            raise MXNetError(f"alert rule {name!r}: unknown kind {kind!r}; "
                             f"expected one of {KINDS}")
        if severity not in SEVERITIES:
            raise MXNetError(f"alert rule {name!r}: unknown severity "
                             f"{severity!r}; expected one of {SEVERITIES}")
        if op not in (">", "<"):
            raise MXNetError(f"alert rule {name!r}: op must be > or <")
        if reduce not in ("sum", "max", "min"):
            raise MXNetError(f"alert rule {name!r}: reduce must be "
                             "sum/max/min")
        if kind == "burn_rate" and not total_family:
            raise MXNetError(f"alert rule {name!r}: burn_rate needs "
                             "total_family")
        self.name = str(name)
        self.family = str(family)
        self.kind = kind
        self.op = op
        self.value = float(value)
        self.for_s = float(for_s)
        self.cooldown_s = float(cooldown_s)
        self.severity = severity
        self.reduce = reduce
        self.labels = dict(labels or {})
        self.window_s = float(window_s)
        self.total_family = total_family
        self.objective = float(objective)
        self.factor = float(factor)
        self.fast_s = float(fast_s)
        self.slow_s = float(slow_s)
        self.doc = doc

    def families(self):
        fams = {self.family}
        if self.total_family:
            fams.add(self.total_family)
        return fams

    def _match(self, rows):
        return [v for labels, v in rows
                if all(labels.get(k) == v2
                       for k, v2 in self.labels.items())]

    def _reduce(self, rows):
        vals = self._match(rows)
        if not vals:
            # a family the registry KNOWS but with no matching cells is
            # a zero counter under sum-reduction; max/min genuinely
            # have no data
            return 0.0 if self.reduce == "sum" else None
        if self.reduce == "max":
            return max(vals)
        if self.reduce == "min":
            return min(vals)
        return sum(vals)

    def _compare(self, v):
        return v > self.value if self.op == ">" else v < self.value

    def _windowed_delta(self, history, now, window):
        """(delta_value, delta_t) against the oldest point within
        ``window`` seconds (monotone counters assumed)."""
        anchor = None
        for t, v in history:
            if now - t <= window:
                anchor = (t, v)
                break
        if anchor is None or not history:
            return None
        t1, v1 = history[-1]
        dt = t1 - anchor[0]
        if dt <= 0:
            return None
        return v1 - anchor[1], dt

    def evaluate(self, samples, history, now):
        """-> (measured_value, condition_bool).  ``samples`` is
        {family: [(labels, value)]}; ``history`` is this rule's engine-
        kept deque (appended by the engine AFTER evaluation)."""
        rows = samples.get(self.family)
        if self.kind == "absence":
            present = bool(self._match(rows or []))
            return (1.0 if present else 0.0), not present
        if self.kind == "threshold":
            v = self._reduce(rows or [])
            if v is None:
                return None, False
            return v, self._compare(v)
        if self.kind == "rate":
            d = self._windowed_delta(history, now, self.window_s)
            if d is None:
                return None, False
            rate = d[0] / d[1]
            return rate, self._compare(rate)
        # burn_rate: history entries are (t, (bad, total))
        def burn(window):
            anchor = None
            for t, (b, tot) in history:
                if now - t <= window:
                    anchor = (b, tot)
                    break
            if anchor is None or not history:
                return None
            b1, tot1 = history[-1][1]
            d_bad, d_total = b1 - anchor[0], tot1 - anchor[1]
            if d_total <= 0:
                return 0.0
            return (d_bad / d_total) / max(1e-12, self.objective)
        fast, slow = burn(self.fast_s), burn(self.slow_s)
        if fast is None or slow is None:
            return None, False
        return fast, (fast >= self.factor and slow >= self.factor)

    def history_point(self, samples):
        """The value the engine appends to this rule's history after a
        tick (None = nothing to record)."""
        if self.kind == "rate":
            rows = samples.get(self.family)
            if rows is None:
                return None  # family unknown yet: no baseline point
            return self._reduce(rows)
        if self.kind == "burn_rate":
            bad = self._reduce(samples.get(self.family) or [])
            total = self._reduce(samples.get(self.total_family) or [])
            if bad is None and total is None:
                return None
            return (bad or 0.0, total or 0.0)
        return None

    def describe(self):
        d = {"name": self.name, "kind": self.kind, "family": self.family,
             "severity": self.severity, "op": self.op, "value": self.value,
             "for_s": self.for_s, "cooldown_s": self.cooldown_s,
             "reduce": self.reduce, "doc": self.doc}
        if self.labels:
            d["labels"] = dict(self.labels)
        if self.kind == "rate":
            d["window_s"] = self.window_s
        if self.kind == "burn_rate":
            d.update({"total_family": self.total_family,
                      "objective": self.objective, "factor": self.factor,
                      "fast_s": self.fast_s, "slow_s": self.slow_s})
        return d


# -- sample sources ------------------------------------------------------------
_PROBES = {}


def register_probe(family, fn):
    """Install a cheap read probe for a family that is not a plain
    registry metric (collector-backed signals).  ``fn()`` -> list of
    ``(labels_dict, value)``."""
    _PROBES[str(family)] = fn


def _serving_counter_probe(key):
    def probe():
        mod = sys.modules.get("mxnet_tpu.serving.metrics")
        if mod is None:
            return []
        out = []
        for name, snap in mod.stats().items():
            v = snap.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out.append(({"server": name}, float(v)))
        return out
    return probe


def _register_default_probes():
    from . import watchdog
    register_probe("mxnet_watchdog_stalled_sections",
                   lambda: [({}, float(len(watchdog.stalled_sections())))])
    register_probe("mxnet_watchdog_fires_total",
                   lambda: [({}, float(watchdog.fires()))])
    register_probe("mxnet_serving_requests_total",
                   _serving_counter_probe("requests_total"))
    register_probe("mxnet_serving_shed_total",
                   _serving_counter_probe("shed_total"))

    def rss_slope_probe():
        from . import resources
        return [({}, float(resources.leak_slope()))]
    register_probe("mxnet_resource_rss_slope_bytes_per_s", rss_slope_probe)

    def snapshot_age_probe():
        from . import fleet
        fn = fleet.provider()
        if fn is None:
            return []
        snap = fn() or {}
        out = []
        if snap.get("mode") == "summary":
            # summary-mode leader (world > DETAIL_AUTO_RANKS): the
            # scrape carries the fleet-wide max age + per-rank ages for
            # anomalous ranks only — exactly what a reduce=max
            # staleness rule needs, without the O(ranks) row fan-out
            age = snap.get("snapshot_age_max_s")
            if isinstance(age, (int, float)):
                out.append(({}, float(age)))
            for rank, v in (snap.get("anomalous") or {}).items():
                age = v.get("snapshot_age_s")
                if isinstance(age, (int, float)):
                    out.append(({"rank": str(rank)}, float(age)))
            return out
        for rank, v in snap.get("ranks", {}).items():
            age = v.get("snapshot_age_s")
            if isinstance(age, (int, float)):
                out.append(({"rank": str(rank)}, float(age)))
        return out
    register_probe("mxnet_fleet_snapshot_age_seconds", snapshot_age_probe)

    def data_queue_depth_probe():
        # pull, never import: a process with no streaming data plane
        # has no rows.  Live pipelines answer only while they make
        # progress — a wedged assembler lets the family go ABSENT, so
        # an absence rule on mxnet_data_queue_depth fires while the
        # train/fit watchdog walks up to its page (docs/data.md)
        mod = sys.modules.get("mxnet_tpu.io_pipeline")
        if mod is None:
            return []
        return mod.queue_depth_samples()
    register_probe("mxnet_data_queue_depth", data_queue_depth_probe)


def _read_family(family):
    probe = _PROBES.get(family)
    if probe is not None:
        try:
            return [(dict(labels), float(v)) for labels, v in probe()]
        except Exception as e:  # noqa: BLE001 — one dead probe must not poison the tick
            log.debug("alert probe %r failed: %s", family, e)
            return []
    from . import REGISTRY
    m = REGISTRY.get(family)
    rows = None
    if m is None and (family.endswith("_count") or family.endswith("_sum")):
        base = family.rsplit("_", 1)[0]
        h = REGISTRY.get(base)
        if h is not None and h.kind == "histogram":
            rows = [s for s in h._samples() if s[0] == family]
    elif m is not None:
        rows = [s for s in m._samples() if s[0] == family]
    if rows is None:
        return None
    return [(dict(s[1]), float(s[2])) for s in rows]


def registry_sampler(families):
    """The default sample source: registered probes first, then plain
    registry metrics (histograms answer for their ``_count``/``_sum``
    derived families).  Unknown families read as absent."""
    out = {}
    for fam in families:
        rows = _read_family(fam)
        if rows is not None:
            out[fam] = rows
    return out


# -- the default rule pack -----------------------------------------------------
def default_rules():
    """The doc alarm table as code (docs/observability.md 'Default rule
    pack'): each entry names the counter it judges and the degraded
    mode it pages on."""
    return [
        AlertRule(
            "watchdog_stall", "mxnet_watchdog_stalled_sections",
            kind="threshold", op=">", value=0, for_s=0.0, cooldown_s=30.0,
            severity="page",
            doc="an armed section is in an active stall episode (the "
                "watchdog fired and no progress since); resolves the "
                "moment the section beats"),
        AlertRule(
            "corrupt_checkpoint", "mxnet_serving_corrupt_ckpt_total",
            kind="rate", op=">", value=0.0, window_s=60.0, for_s=0.0,
            cooldown_s=60.0, severity="page",
            doc="a committed checkpoint step failed verification during "
                "hot-reload within the last window; the old version "
                "keeps serving but publishes are broken"),
        AlertRule(
            "spill_storm", "mxnet_serving_router_spill_total",
            kind="rate", op=">", value=1.0, window_s=10.0, for_s=2.0,
            cooldown_s=30.0, severity="warn",
            doc="sustained router spill rate: a replica is persistently "
                "refusing traffic while siblings absorb it"),
        AlertRule(
            "shed_burn_rate", "mxnet_serving_shed_total",
            kind="burn_rate", total_family="mxnet_serving_requests_total",
            objective=0.05, factor=2.0, fast_s=60.0, slow_s=300.0,
            for_s=0.0, cooldown_s=120.0, severity="page",
            doc="shed-ratio SLO burn: sheds are consuming the 5% error "
                "budget at >= 2x in BOTH the fast and slow windows"),
        AlertRule(
            "retrace_ratchet", "mxnet_compile_traces_total",
            kind="rate", op=">", value=0.5, window_s=30.0, for_s=10.0,
            cooldown_s=120.0, severity="warn",
            labels={"reason": "request"},
            doc="sustained REQUEST-path retraces: compiles are running "
                "on the hot path (deliberate warmup/build traces are "
                "excluded by the reason label; docs/compile.md runbook)"),
        AlertRule(
            "rss_slope", "mxnet_resource_rss_slope_bytes_per_s",
            kind="threshold", op=">", value=8e6, for_s=10.0,
            cooldown_s=120.0, severity="warn",
            doc="host RSS climbing at > 8 MB/s over the sampler window "
                "— a leak, or a workload outgrowing the host"),
        AlertRule(
            "snapshot_stale", "mxnet_fleet_snapshot_age_seconds",
            kind="threshold", op=">", value=30.0, for_s=5.0,
            cooldown_s=60.0, severity="warn", reduce="max",
            doc="a fleet rank's last telemetry push is stale: its "
                "reporter wedged or the rank is dying quietly"),
        AlertRule(
            "fleet_merge_slow", "mxnet_fleet_merge_seconds_sum",
            kind="rate", op=">", value=0.05, window_s=30.0, for_s=10.0,
            cooldown_s=120.0, severity="warn",
            doc="the fleet leader is spending a sustained > 5% of wall "
                "time merging telemetry pushes (merge seconds accruing "
                "at > 0.05 s/s over the lookback): delta encoding is "
                "off/ineffective or the store is degenerating to full "
                "re-merges — docs/observability.md 'the leader is hot' "
                "runbook"),
        AlertRule(
            "nonfinite_window", "mxnet_numerics_nonfinite_windows_total",
            kind="rate", op=">", value=0.0, window_s=60.0, for_s=0.0,
            cooldown_s=60.0, severity="page",
            doc="a train window contained non-finite gradients/params/"
                "loss within the last minute: the model is diverging or "
                "the data is poisoned — the forensic "
                "mxnet-numerics-*.json dump names the window "
                "(docs/observability.md numerics runbook)"),
        AlertRule(
            "grad_norm_explosion", "mxnet_numerics_grad_norm",
            kind="rate", op=">", value=1.0, window_s=30.0, for_s=5.0,
            cooldown_s=120.0, severity="warn",
            doc="the global gradient norm is climbing sustainedly "
                "(> 1/s over the lookback): an exploding-gradient "
                "trajectory headed for non-finite; tune the bound to "
                "the model's scale via MXNET_ALERT_RULES"),
        AlertRule(
            "loss_spike", "mxnet_numerics_loss",
            kind="rate", op=">", value=0.5, window_s=30.0, for_s=5.0,
            cooldown_s=120.0, severity="warn",
            doc="the loss proxy is rising sustainedly instead of "
                "converging — divergence judged before it reaches "
                "non-finite; tune the bound per model via "
                "MXNET_ALERT_RULES"),
        AlertRule(
            "data_starved", "mxnet_data_wait_seconds_sum",
            kind="rate", op=">", value=0.3, window_s=30.0, for_s=10.0,
            cooldown_s=120.0, severity="warn",
            doc="the train thread is spending a sustained > 30% of "
                "wall time blocked on the input pipeline (data_wait "
                "seconds accruing at > 0.3 s/s over the lookback): "
                "training is data-bound — raise MXNET_DATA_WORKERS / "
                "queue depth or shrink the decode (docs/data.md "
                "'training is data-bound' runbook)"),
        AlertRule(
            "kernel_fallback", "mxnet_kernel_fallback_total",
            kind="rate", op=">", value=0.0, window_s=60.0, for_s=0.0,
            cooldown_s=120.0, severity="warn",
            doc="a kernels-subsystem lookup served the reference "
                "implementation instead of a Pallas config within the "
                "last window — a correctness-gate failure or aborted "
                "autotune (docs/kernels.md runbook); numerics stay "
                "correct, the tuned speed is gone"),
    ]


def parse_rules(spec):
    """``MXNET_ALERT_RULES`` grammar — ``;``-separated arms::

        name=family>value[:for=S][:cooldown=S][:severity=warn|page]
                         [:reduce=sum|max|min][:kind=threshold|rate|absence]
                         [:window=S]

    (``<`` for lower bounds).  Parsed rules are appended to the default
    pack; a name collision replaces the default rule.
    """
    rules = []
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise MXNetError(f"alert rule spec {part!r}: expected "
                             "name=family<op>value[...]")
        name, rhs = part.split("=", 1)
        fields = rhs.split(":")
        head, opts = fields[0].strip(), fields[1:]
        op = ">" if ">" in head else ("<" if "<" in head else None)
        if op is None:
            raise MXNetError(f"alert rule spec {part!r}: no > or < bound")
        family, value = head.split(op, 1)
        kw = {"op": op, "value": float(value)}
        keymap = {"for": ("for_s", float), "cooldown": ("cooldown_s", float),
                  "severity": ("severity", str), "reduce": ("reduce", str),
                  "kind": ("kind", str), "window": ("window_s", float)}
        for opt in opts:
            if "=" not in opt:
                raise MXNetError(f"alert rule spec {part!r}: bad option "
                                 f"{opt!r}")
            k, v = opt.split("=", 1)
            if k.strip() not in keymap:
                raise MXNetError(f"alert rule spec {part!r}: unknown "
                                 f"option {k!r}")
            field, cast = keymap[k.strip()]
            kw[field] = cast(v.strip())
        rules.append(AlertRule(name.strip(), family.strip(), **kw))
    return rules


# -- the engine ----------------------------------------------------------------
_HISTORY_POINTS = 2048
_TRANSITIONS_KEPT = 16


class AlertEngine:
    """Evaluates a rule set against a sample source; owns each rule's
    lifecycle state.  ``tick(now=...)`` takes an explicit clock so the
    hysteresis / cooldown / burn-window tests are deterministic."""

    def __init__(self, rules=None, sampler=None):
        _register_default_probes()  # idempotent: default-pack sources
        self.rules = list(default_rules() if rules is None else rules)
        names = [r.name for r in self.rules]
        if len(set(names)) != len(names):
            raise MXNetError(f"duplicate alert rule names: {names}")
        self._sampler = sampler if sampler is not None else registry_sampler
        self._lock = threading.Lock()
        # serializes tick() bodies (the per-rule history deques are
        # single-writer) WITHOUT holding self._lock across user rule
        # code — self._lock guards engine state only and is never held
        # while rule.evaluate/describe or another subsystem runs
        self._tick_lock = threading.Lock()
        self._states = {r.name: self._fresh_state() for r in self.rules}
        self._history = {r.name: collections.deque(maxlen=_HISTORY_POINTS)
                         for r in self.rules}
        self.ticks = 0
        self._metrics_ready = False

    @staticmethod
    def _fresh_state():
        return {"state": "inactive", "since": None, "pending_since": None,
                "fired_at": None, "resolved_at": None, "value": None,
                "transitions": 0, "fired_total": 0,
                "recent": collections.deque(maxlen=_TRANSITIONS_KEPT)}

    def add_rule(self, rule, replace=True):
        with self._lock:
            for i, r in enumerate(self.rules):
                if r.name == rule.name:
                    if not replace:
                        raise MXNetError(f"alert rule {rule.name!r} exists")
                    self.rules[i] = rule
                    break
            else:
                self.rules.append(rule)
            self._states.setdefault(rule.name, self._fresh_state())
            self._history.setdefault(
                rule.name, collections.deque(maxlen=_HISTORY_POINTS))

    # -- metrics side effects ------------------------------------------------
    def _metrics(self):
        from . import REGISTRY
        return (REGISTRY.counter(
                    "mxnet_alert_transitions_total",
                    "alert rule lifecycle transitions, by rule and "
                    "target state"),
                REGISTRY.gauge(
                    "mxnet_alert_state",
                    "one-hot alert rule state (1 = the labelled state "
                    "holds), by rule"),
                REGISTRY.gauge(
                    "mxnet_alerts_firing",
                    "count of currently-firing alert rules, by severity"))

    def _transition(self, rule, st, to, now, value):
        """Mutate ``st`` (caller holds ``self._lock``) and return the
        emission record — metric/flight/log side effects run OUTSIDE
        the lock (``_emit_transition``): the flight ring, the registry,
        and the logging subsystem each own locks of their own, and
        holding the engine lock into them is an ordering edge the
        lock-order-cycle rule rightly flags."""
        frm = st["state"]
        st["state"] = to
        st["since"] = now
        st["transitions"] += 1
        st["recent"].append({"t": time.time(), "mono": now, "from": frm,
                             "to": to, "value": value})
        if to == "pending":
            st["pending_since"] = now
        elif to == "firing":
            st["fired_at"] = now
            st["fired_total"] += 1
        elif to == "resolved":
            st["resolved_at"] = now
        return (rule, frm, to, value)

    def _emit_transition(self, rule, frm, to, value):
        counter, state_gauge, _firing_gauge = self._metrics()
        counter.inc(labels={"rule": rule.name, "to": to})
        for s in STATES:
            state_gauge.set(1.0 if s == to else 0.0,
                            labels={"rule": rule.name, "state": s})
        from . import flight
        if to == "firing":
            severity = "error" if rule.severity == "page" else "warn"
        else:
            severity = "info"
        flight.record("alert", f"{rule.name}:{frm}->{to}",
                      severity=severity, rule=rule.name, to=to,
                      value=value, threshold=rule.value,
                      rule_severity=rule.severity)
        log.log(logging.WARNING if to == "firing" else logging.INFO,
                "alert %s: %s -> %s (value=%s threshold=%s)",
                rule.name, frm, to, value, rule.value)

    # -- evaluation ----------------------------------------------------------
    def tick(self, now=None):
        """One evaluation pass over every rule; returns the number of
        state transitions it caused.

        Lock protocol: ``rule.history_point``/``rule.evaluate`` are
        USER code (``add_rule`` accepts arbitrary objects) and run
        under ``_tick_lock`` only — a rule that introspects the engine
        (``state()``/``firing()``) must not deadlock on the engine
        lock.  ``self._lock`` is held only to snapshot the rule list
        and to apply state transitions; metric/flight/log emission
        happens after it is released."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            rules = list(self.rules)
        families = set()
        for r in rules:
            families |= r.families()
        try:
            samples = self._sampler(families)
        except Exception as e:  # noqa: BLE001 — a broken sampler must not kill the evaluation thread
            log.warning("alert sampler failed: %s", e)
            return 0
        moved = 0
        events = []
        with self._tick_lock:
            with self._lock:
                self.ticks += 1
                histories = {r.name: self._history[r.name]
                             for r in rules if r.name in self._history}
            evals = []
            for rule in rules:
                history = histories.get(rule.name)
                if history is None:
                    continue
                point = rule.history_point(samples)
                if point is not None:
                    history.append((now, point))
                value, cond = rule.evaluate(samples, history, now)
                evals.append((rule, value, cond))
            with self._lock:
                for rule, value, cond in evals:
                    st = self._states.get(rule.name)
                    if st is None:
                        continue
                    st["value"] = value
                    state = st["state"]
                    if state == "inactive":
                        if cond:
                            events.append(self._transition(
                                rule, st, "pending", now, value))
                            if rule.for_s <= 0:
                                events.append(self._transition(
                                    rule, st, "firing", now, value))
                    elif state == "pending":
                        if not cond:
                            events.append(self._transition(
                                rule, st, "inactive", now, value))
                        elif now - st["pending_since"] >= rule.for_s:
                            events.append(self._transition(
                                rule, st, "firing", now, value))
                    elif state == "firing":
                        if not cond:
                            events.append(self._transition(
                                rule, st, "resolved", now, value))
                    elif state == "resolved":
                        cooled = (now - (st["resolved_at"] or now)
                                  >= rule.cooldown_s)
                        if cond and cooled:
                            events.append(self._transition(
                                rule, st, "pending", now, value))
                            if rule.for_s <= 0:
                                events.append(self._transition(
                                    rule, st, "firing", now, value))
                        elif not cond and cooled:
                            events.append(self._transition(
                                rule, st, "inactive", now, value))
                counts = {s: 0 for s in SEVERITIES}
                for rule in rules:
                    st = self._states.get(rule.name)
                    if st is not None and st["state"] == "firing":
                        counts[rule.severity] += 1
            moved = len(events)
            for rule, frm, to, value in events:
                self._emit_transition(rule, frm, to, value)
            _c, _g, firing_gauge = self._metrics()
            for sev, n in counts.items():
                firing_gauge.set(n, labels={"severity": sev})
        return moved

    # -- read side -----------------------------------------------------------
    def state(self, name):
        with self._lock:
            st = self._states[name]
            return {k: (list(v) if isinstance(v, collections.deque) else v)
                    for k, v in st.items()}

    def firing(self, severity=None):
        """Names of currently-firing rules (optionally one severity)."""
        with self._lock:
            return sorted(
                r.name for r in self.rules
                if self._states[r.name]["state"] == "firing"
                and (severity is None or r.severity == severity))

    def transitions(self, name):
        with self._lock:
            return list(self._states[name]["recent"])

    def alerts_json(self):
        """The ``GET /alerts.json`` payload.  ``rule.describe()`` is
        user code and runs outside the engine lock (state is snapshot
        first)."""
        with self._lock:
            rule_list = list(self.rules)
            snap = {}
            for rule in rule_list:
                st = self._states[rule.name]
                snap[rule.name] = {
                    "state": st["state"], "value": st["value"],
                    "since": st["since"],
                    "transitions": st["transitions"],
                    "fired_total": st["fired_total"],
                    "recent": list(st["recent"])}
            ticks = self.ticks
        rules = []
        for rule in rule_list:
            d = rule.describe()
            d.update(snap[rule.name])
            rules.append(d)
        firing = sorted(r.name for r in rule_list
                        if snap[r.name]["state"] == "firing")
        pages = sorted(r.name for r in rule_list
                       if snap[r.name]["state"] == "firing"
                       and r.severity == "page")
        return {"time": time.time(), "enabled": _armed,
                "ticks": ticks, "rules": rules,
                "firing": firing, "pages": pages}


# -- module-level singleton + evaluation thread --------------------------------
def engine():
    """The process-wide engine (created on first use: default pack +
    any ``MXNET_ALERT_RULES`` extras)."""
    global _engine
    with _lock:
        if _engine is None:
            eng = AlertEngine()
            from .. import config as _config
            for rule in parse_rules(_config.get("MXNET_ALERT_RULES")):
                eng.add_rule(rule)
            _engine = eng
        return _engine


def set_engine(eng):
    """Install a specific engine as the process-wide one (tests; None
    resets to lazy default)."""
    global _engine
    with _lock:
        _engine = eng


def tick(now=None):
    """Module-level tick: one global check when the engine is disarmed
    (< 1 µs — the span/trace/failpoint bar), a full evaluation pass
    otherwise."""
    if not _armed:
        return 0
    return engine().tick(now=now)


def enabled():
    return _armed


def start(interval_s=None):
    """Arm the engine and start the evaluation thread.  ``interval_s``
    defaults to ``MXNET_ALERTS`` (0 = leave disarmed)."""
    global _armed, _thread, _stop
    if interval_s is None:
        from .. import config as _config
        interval_s = float(_config.get("MXNET_ALERTS"))
    interval_s = float(interval_s)
    if interval_s <= 0:
        return False
    eng = engine()  # build before arming: first tick must not race init
    _armed = True
    with _lock:
        if _thread is not None and _thread.is_alive():
            return True
        _stop = threading.Event()
        _thread = threading.Thread(
            target=_loop, args=(eng, interval_s), daemon=True,
            name="mx-alert-engine")
        _thread.start()
    return True


def stop():
    """Disarm and stop the evaluation thread (state is kept)."""
    global _armed, _thread, _stop
    _armed = False
    with _lock:
        stop_ev, _stop = _stop, None
        thread, _thread = _thread, None
    if stop_ev is not None:
        stop_ev.set()
    if thread is not None:
        thread.join(timeout=5)


def _loop(eng, interval_s):
    while True:
        with _lock:
            stop_ev = _stop
        if stop_ev is None or stop_ev.wait(max(0.01, interval_s)):
            return
        try:
            eng.tick()
        except Exception as e:  # noqa: BLE001 — the evaluation loop must survive any one bad tick
            log.warning("alert tick failed: %s", e)


def firing(severity=None):
    """Currently-firing rule names; cheap and safe when disarmed."""
    with _lock:
        eng = _engine
    if not _armed or eng is None:
        return []
    return eng.firing(severity)


def firing_pages():
    """Firing page-severity rules — the ``/healthz`` readiness input
    (warn-severity alerts deliberately stay out of liveness)."""
    return firing("page")


def alerts_json():
    """The ``/alerts.json`` payload (meaningful on any process: a
    disarmed engine reports its rule pack with enabled=false)."""
    return engine().alerts_json()


def _reset_for_tests():
    """Stop the thread, drop the singleton, forget probes."""
    stop()
    set_engine(None)
