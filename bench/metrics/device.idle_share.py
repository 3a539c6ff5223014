"""Share of the traced window in which no operation ran on the device
(1 - union of the ``XLA Ops`` intervals over the window), batch cells."""


def read(run):
    share = run.trace.idle_share if run.trace else None
    return None if share is None else 100.0 * share
