"""Chip benchmark of the BFP serving path (see ``BENCHMARK.json``).

``run.py`` is the one command.  Everything that belongs to one model
configuration, traffic mix or metric sits in a file of its own, found by
the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the sizes and the precision as run, with the
  plain float32 reference beside it in ``configs/<reference>.py``;
* ``traffic/<mix>.json``: the parameters the one generator in
  ``drive.py`` reads, with the arrival process it names in
  ``traffic/<arrivals>.py``;
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.
"""
