"""operators: device milliseconds a step in the chunked scans: ops under
``granite/mamba/ssd`` (Mamba-2's state-space scan, also inside
``nemotron/mamba``) or ``solar/kda/scan`` (the delta rule); forward,
backward and recomputation together."""
import scoperead


def read(data):
    return scoperead.scopes().ms_per_step(data, "classes", "scan")
