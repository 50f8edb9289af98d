"""input staging: host milliseconds per step in lane ``h2d_stage`` (the
time to issue the copy of the next batch or super-batch), under the 90 %
rule of the lanes."""
import benchcore as C


def read(data):
    share = C.lane_share_pct(data, ("h2d_stage",))
    if share is None:
        return None
    lanes = data["lanes"]
    return lanes["lanes"]["h2d_stage"] / lanes["steps"] * 1e3
