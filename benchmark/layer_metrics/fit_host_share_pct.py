"""fit loop: share of the window's wall time the train thread spent in
the host lanes of ``telemetry.steps`` (waiting for data, staging,
dispatching, metric math).  Host clock of the program's own lanes, used
only where the lanes account for at least 90 % of the wall."""
import benchcore as C


def read(data):
    return C.lane_share_pct(data, ("data_wait", "h2d_stage",
                                   "step_dispatch", "metric_flush"))
