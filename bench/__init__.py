"""On-chip benchmark of the trainer and the serving engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own, found by its name:

- ``bench/configs/<config>.json``: the model configuration as it is run;
- ``bench/traffic/<traffic>.json``: a traffic mix, read by the generator
  ``bench/traffic/<kind>.py`` and driven by ``bench/drivers/<driver>.py``;
- ``bench/metrics/<metric>.py``: a reader that reduces one run to one
  per-layer metric, or to nothing where it finds nothing to read;
- ``bench/limits/<cell>.json``: the limits of the comparison that decides
  ``correct``;
- ``bench/references/<reference>.py``: the plain float32 reference a
  configuration names.
"""
