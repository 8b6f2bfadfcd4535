"""The on-chip benchmark of the fleet detection service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json``.  Everything that belongs to one
configuration, traffic mix or metric is a file of its own, found by the name
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the model configuration as it is run;
  its plain reference is :mod:`bench.reference` (SINT) or
  :mod:`bench.reference_real` (REAL), and where it sets ``adapt``, the
  threshold recalibration of :mod:`bench.reference_adapt`.
* ``bench/traffic/<traffic>.json``: the parameters :mod:`bench.traffic`
  generates the traffic from.
* ``bench/metrics/<metric>.py``: a reader with ``read(ctx)`` that returns
  the metric's value, or None where it finds nothing to read.
"""
