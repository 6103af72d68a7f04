"""The on-chip benchmark of the federation: one data-driven harness.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix or per-layer metric sits in a file of its own,
found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run; its
  ``family`` names ``bench/configs/<family>.py`` (how the program is built
  from it) and ``bench/configs/<family>_reference.py`` (its plain reference).
* ``bench/traffic/<traffic>.json``: the federation's shape, read by the one
  generator in ``bench/traffic.py``.
* ``bench/limits/<workload>.json``: the limits of the comparison that
  decides ``correct``, with the readings they were set from.
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
