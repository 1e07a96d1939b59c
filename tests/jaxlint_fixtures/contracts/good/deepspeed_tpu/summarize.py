"""Consumer side: reads the emitted sync scalar fixture_wait_s."""


def summarize(scalars):
    return scalars.get("fixture_wait_s")
