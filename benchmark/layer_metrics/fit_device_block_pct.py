"""fit loop: share of the window's wall time the train thread spent
blocked on the device's results (lane ``device_block``), under the same
90 % rule as the host lanes."""
import benchcore as C


def read(data):
    return C.lane_share_pct(data, ("device_block",))
