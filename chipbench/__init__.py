"""Chip benchmark of Dif-AltGDmin training and personalization serving.

Run one cell of ``BENCHMARK.json`` from the root of a checkout::

    python3 -m chipbench.run --workload exp1.train --seed 7 --seconds 10 --trace 0

Everything is found by name, so a new cell adds files and edits none:

* a configuration is ``chipbench/configs/<config>.json``: the deployment
  (the ``ExperimentSpec`` fields), its source, ``reduced``, ``assumed``
  and the matmul precision it states;
* a traffic mix is ``chipbench/traffic/<traffic>.json``: the name of a
  driver under ``chipbench/drivers/`` and that driver's parameters;
* the limits of a cell's correctness check are
  ``chipbench/checks/<workload>.json``, each with the readings it was
  set from;
* a per-layer metric is ``chipbench/metrics/<metric>.py`` with one
  function ``read(rec)`` over the run's spans, reduced device trace and
  required work (``harness.RunRecord``).  It returns the value, or a
  dict of the value and labels such as ``bound``, or ``None``, which
  leaves the metric out of the result line.

The plain references the checks compare against live in
``chipbench/reference/`` and import nothing of the system under test.
"""
