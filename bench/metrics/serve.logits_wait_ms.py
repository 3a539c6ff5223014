"""Host time per forward spent waiting for the logits to reach the host
(the device finishing the forward, then the copy out): the window's
``host_ns.serve.logits_wait`` (``CnnServeEngine.stats``,
docs/tracing.md) over its ``forwards``.  None where the program counts
no forwards."""


def read(run):
    n = run.stats.get("forwards", 0)
    return run.stats.get("host_ns.serve.logits_wait", 0) / n * 1e-6 if n \
        else None
