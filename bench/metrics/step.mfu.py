"""Model-operation utilisation of the whole step on the device: the
model's operations per forward at its bucket (2 x the MACs of every conv
and fc, ``bench/work.py``), over the device time of the forward's runs
in the traced window (``XLA Modules``), over the int8 peak."""
from bench.work import model_work


def read(run):
    runs = run.trace.program_runs if run.trace else []
    if not runs or not run.peaks:
        return None
    steps = run.record.steps
    ops = sum(model_work(run.sites, s.bucket)["ops"] for s in steps) \
        / len(steps)
    return 100.0 * ops / (sum(runs) / len(runs)) \
        / run.peaks["int8_ops_per_s"]
