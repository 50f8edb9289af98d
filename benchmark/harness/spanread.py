"""What the readers of the program's own spans share.

``mxnet_tpu.telemetry`` keeps every finished span in memory
(``telemetry.span_records()``): name, start and end, self time, parent,
thread, the step id that all spans of one training step (or one scanned
window) share, and the deltas of the counters that counted while the span
was innermost.  A reader runs in the run's process after the run, so it
reads them directly.  It looks at the last ``data["trace"]["steps"]``
steps only (that many over ``steps_per_sync`` step ids in the scanned
cell): the profiled stretch, the same the device metrics are taken over.

Every function returns None where the program has no such span or
counter, as a program from before the spans has not.
"""
from __future__ import annotations

import statistics

# the span around the call of the jitted step program, by entry point
DISPATCH = ("fit/step/fused_dispatch", "fit/step/scan_dispatch",
            "spmd/step/dispatch")


def steps_per_id(data):
    return int(data["cell"]["steps_per_sync"])


def last_steps(data, names):
    """``{step id: [record, ...]}`` of the spans named in ``names``,
    over the last profiled step ids in which any of them appears."""
    from mxnet_tpu import telemetry
    records = getattr(telemetry, "span_records", None)
    n_ids = int(data["trace"].get("steps") or 0) // steps_per_id(data)
    if records is None or n_ids < 1:
        return None
    by_step = {}
    for rec in records():
        if rec["name"] in names:
            by_step.setdefault(rec["step"], []).append(rec)
    if not by_step:
        return None
    return {step: by_step[step] for step in sorted(by_step)[-n_ids:]}


def median_ms_per_step(data, names, self_time=False):
    """Median over step ids of the milliseconds the named spans took
    there, per training step; with ``self_time`` what their child spans
    cover is left out."""
    by_step = last_steps(data, names)
    if by_step is None:
        return None
    per_id = [sum(rec["self_ns"] if self_time
                  else rec["end_ns"] - rec["start_ns"] for rec in recs)
              for recs in by_step.values()]
    return statistics.median(per_id) / 1e6 / steps_per_id(data)


def counter_per_step(data, counter, names, per_call=False):
    """The counter's delta inside the named spans over the last profiled
    step ids, per training step, or with ``per_call`` per span record
    that counted."""
    by_step = last_steps(data, names)
    if by_step is None:
        return None
    hits = [rec["counts"][counter] for recs in by_step.values()
            for rec in recs if rec["counts"] and counter in rec["counts"]]
    if not hits:
        return None
    if per_call:
        return sum(hits) / len(hits)
    return sum(hits) / (len(by_step) * steps_per_id(data))
