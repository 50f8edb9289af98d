"""release-under-wrong-lock — acquire and paired release run under
different lock sets in a threaded subsystem.

Origin: ISSUE 18's triage of the serving KV accounting.
``KVSlotPool`` deliberately charges the ledger AFTER dropping its own
lock (PR 16: never call a metrics/accounting layer under a pool lock —
the exporter scrapes it).  A release path that slips the paired
``LEDGER.release`` back UNDER the pool lock reintroduces exactly the
lock-order hazard the design dodged, and it only deadlocks when the
exporter scrape lands mid-release — a once-a-week soak flake.  More
generally: when the acquire site of a paired protocol runs under lock
set X and the release site under a different set Y, either the acquire
leaked a lock requirement the release doesn't honor (torn state), or
the release takes locks the acquire proved unnecessary (deadlock
surface).

The lifecycle engine emits every (acquire, release) site pairing it
proved for a resource, with each site's held-lock set from the PR 15
summaries.  This rule fires only when:

* the function lives in a threaded subsystem (same
  ``THREADED_PREFIXES`` gate as lock-order-cycle — single-threaded
  tools code can't deadlock), and
* the held sets DIFFER (symmetric difference non-empty).

Near-misses that stay silent: both sites lock-free, both sites under
the identical lock (the common ``with self._lock:`` pattern around
both halves), pairings where either site's held-set is unknown, and
the manual-lock protocol itself (its acquire/release ARE the lock).
"""
from __future__ import annotations

from ..core import GraphRule, register_graph_rule
from ..lifecycle import lifecycle_report
from .lock_order_cycle import THREADED_PREFIXES


@register_graph_rule
class ReleaseWrongLockRule(GraphRule):
    id = "release-under-wrong-lock"
    severity = "warning"
    doc = ("paired resource release runs under a different lock set "
           "than its acquire in a threaded subsystem (deadlock "
           "surface or torn accounting)")

    def run(self, program):
        findings = []
        seen = set()
        for entry in lifecycle_report(program).pairs:
            fs = entry.fs
            if not fs.path.startswith(THREADED_PREFIXES):
                continue
            acq_held = frozenset(entry.detail["acq_held"])
            rel_held = frozenset(entry.detail["rel_held"])
            if acq_held == rel_held:
                continue
            key = (fs.id, entry.label, entry.lineno)
            if key in seen:
                continue
            seen.add(key)
            findings.append(self.finding(
                fs.path, entry.lineno, entry.col,
                f"{entry.proto} resource '{entry.label}' is released "
                f"at line {entry.lineno} under locks "
                f"[{', '.join(sorted(rel_held)) or 'none'}] but was "
                f"acquired at line {entry.detail['acq_line']} under "
                f"[{', '.join(sorted(acq_held)) or 'none'}] in "
                f"{fs.qual}() — acquire and release must agree on "
                "their lock discipline",
                symbol=f"{fs.qual}:{entry.proto}:{entry.label}"))
        return findings
