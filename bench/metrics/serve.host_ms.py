"""Host time per forward of the serving step outside the wait for the
device: the window's ``host_ns.serve.admit``, ``.batch_in``,
``.dispatch`` and ``.finish`` (``CnnServeEngine.stats``, docs/tracing.md)
over its ``forwards``.  None where the program counts no forwards."""

PHASES = ("admit", "batch_in", "dispatch", "finish")


def read(run):
    n = run.stats.get("forwards", 0)
    if not n:
        return None
    return sum(run.stats.get(f"host_ns.serve.{p}", 0) for p in PHASES) \
        / n * 1e-6
