"""Chip benchmark of the NeedleTail serving path.

One run of one cell (``BENCHMARK.json`` ``workloads``) on the chip::

    python3 chipbench/run.py --workload nt71-browse --seed 7 --seconds 30 --trace 0

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by name:

* ``configs/<config>.json`` — a deployment's table: generator, sizes,
  ``source``, ``reduced``, ``assumed`` and the guarantees it states;
* ``tables/<generator>.py`` — the seeded table generator a config names;
* ``traffic/<traffic>.json`` — a traffic mix, read by :mod:`chipbench.traffic`:
  classes of LIMIT (exemplar) requests and of online aggregates
  (``"kind": "aggregate"``, with ``measure``, ``error_slo`` and ``k``); a mix
  that holds an aggregate class states ``limits.agg_outside_4se`` (the
  module's docstring gives the keys and the rule for that limit);
* ``arrivals/<process>.py`` — an arrival process a mix names, ``make(...)``;
* ``metrics/<metric>.py`` — a per-layer metric reader, ``read(ctx)``, with an
  optional ``probe(served)`` the harness takes at fixed moments of the window.

The yardstick (traffic generation, the full-scan reference and the checks,
trace reduction, byte functions and the peaks table) lives here too; from the
program the benchmark takes only the system under test and its spans,
counters and kernel names.
"""
