"""On-chip benchmark of the tiered-KV serving path.

Everything here is found by name from ``BENCHMARK.json``: a cell names a
configuration (``configs/<name>.json``), a traffic mix
(``traffic/<name>.json``, read by ``generators/<kind>.py``) and its
correctness limits (``cells/<cell>.json``); each metric is a reader module
``metrics/<metric>.py``. ``run.py`` is the entry point.
"""
