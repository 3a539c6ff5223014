"""Share of the MXU work the conv launches issue that the model's convs
need: the convs' MACs at each step's bucket (``bench/work.py``) over the
window's ``conv_macs_issued`` (``CnnServeEngine.stats``, docs/tracing.md:
each launch issues B * OHp * OW * Kp * ceil(OC / 128) * 128, padded rows,
K and output columns included).  None where the program counts no issued
MACs."""
from bench.work import model_work


def read(run):
    issued = run.stats.get("conv_macs_issued", 0)
    if not issued:
        return None
    need = sum(model_work(run.sites, s.bucket, kind="conv")["macs"]
               for s in run.record.steps)
    return 100.0 * need / issued
