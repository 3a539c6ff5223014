"""Share of the conv kernels' roofline: the least time the chip could take
for the convs of every forward in the traced window (``bench/work.py``:
the larger of int8 operations over the int8 peak and BFP-8 bytes over
the HBM bandwidth, at each forward's bucket) over the device time of the
conv kernels (``class_totals["conv"]``)."""
from bench.work import least_seconds, model_work


def read(run):
    t = run.trace.class_totals.get("conv") if run.trace else None
    if not t or not run.peaks:
        return None
    least = sum(least_seconds(model_work(run.sites, s.bucket, kind="conv"),
                              run.peaks) for s in run.record.steps)
    return 100.0 * least / t
