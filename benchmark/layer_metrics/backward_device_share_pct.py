"""train step: the share of the device's op time a step that the backward
pass takes (name stacks under ``transpose(...)``; recomputation is
counted apart).  A reading, not a goal: ``better: lower`` by
convention only."""
import scoperead


def read(data):
    return scoperead.scopes().share_pct(data, "phases", "backward")
