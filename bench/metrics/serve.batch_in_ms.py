"""Host time per forward to turn the group's images into the sharded
device batch: the window's ``host_ns.serve.batch_in``
(``CnnServeEngine.stats``, docs/tracing.md) over its ``forwards``.  None
where the program counts no forwards."""


def read(run):
    n = run.stats.get("forwards", 0)
    return run.stats.get("host_ns.serve.batch_in", 0) / n * 1e-6 if n \
        else None
