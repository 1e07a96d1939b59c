"""Metric plane: a registry counter with HELP text and a sync scalar,
both consumed elsewhere (docs bullet / summarize row)."""


class Recorder:
    def __init__(self, reg):
        self.ticks = reg.counter("fixture_ticks_total",
                                 "ticks observed by the loop")

    def on_sync(self, scalars, wait):
        scalars["fixture_wait_s"] = wait
