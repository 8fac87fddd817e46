"""On-chip benchmark of the repo's training and serving paths.

Run one cell with ``python -m bench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout. Everything a
cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json`` (read by the generator it names),
``workloads/<cell>.json`` and ``metrics/<metric>.py``.
"""
