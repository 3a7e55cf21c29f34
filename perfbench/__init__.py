"""The chip benchmark: one command runs one cell once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``BENCHMARK.json`` at the root of the checkout names the cells.  Everything
that belongs to one configuration, one traffic mix or one per-layer metric
is a file of its own, found by name:

* ``configs/<config>.json``: the model's published sizes, as run;
* ``mixes/<traffic>.json``: the traffic's parameters; its ``generator``
  key names the general generator, ``generators/<generator>.py``, that
  reads them;
* ``metrics/<metric>.py``: a reader with ``read(ctx)`` that takes one
  per-layer metric from the run's counters or its device trace.

The yardstick lives here too, so that the program cannot change it:
the trace reduction (``trace.py``), the operation and byte counts
(``counts.py``), the peaks table (``peaks.json``) and the comparisons that
decide ``correct`` (in each generator).
"""
