"""Reads a sync scalar no engine ever emits (JL102)."""


def summarize(scalars):
    return scalars.get("fixture_ghost_s")
