"""Share of the window's forwards whose input batch was built while the
forward before them ran: 100 x ``batches_staged`` over ``forwards``
(``CnnServeEngine.stats``, docs/tracing.md).  None where the program
counts no forwards, or keeps no such counter."""


def read(run):
    n = run.stats.get("forwards", 0)
    if not n or "batches_staged" not in run.stats:
        return None
    return 100.0 * run.stats["batches_staged"] / n
