"""Set-up: from the start of the harness to the window's open (imports,
weights, bind, image pool, warm-up of every bucket)."""


def read(run):
    return run.setup_s
