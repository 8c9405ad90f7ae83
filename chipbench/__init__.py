"""chipbench — the repository's benchmark on the chip.

One command runs one cell once (``python3 -m chipbench.run``); everything
that belongs to one deployment, one traffic mix or one per-layer metric is
a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — a deployment: sizes, guarantees, embedder
  arguments;
* ``workloads/<cell>.json`` — a traffic mix: loop kind, clients or rate,
  skew, warm-up, poll interval;
* ``layer_metrics/<metric>.py`` — one ``read(run) -> float | None``.

The yardstick (load generation, percentiles, trace reduction, the plain
reference, the gates that decide ``correct``) lives here and takes from
the program only the system under test and its counters.
"""
