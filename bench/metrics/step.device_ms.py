"""Device time of one forward: the mean over the traced window's runs of
the served program (``XLA Modules`` events of the bound forward)."""


def read(run):
    runs = run.trace.program_runs if run.trace else []
    return 1e3 * sum(runs) / len(runs) if runs else None
