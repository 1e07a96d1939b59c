"""Metric plane violations: a counter with no HELP text that nothing
consumes, and a sync scalar nothing reads (JL102)."""


class Recorder:
    def __init__(self, reg):
        self.ticks = reg.counter("fixture_orphan_total")

    def on_sync(self, scalars):
        scalars["fixture_dead_s"] = 1.0
