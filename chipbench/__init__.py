"""On-chip benchmark of the kernel-graph system.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything that belongs to a
configuration, a traffic mix or a per-layer metric is a file of its own,
found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   -- the deployment (sizes, kernel, tenants);
* ``traffic/<mix>.json``      -- a traffic mix's parameters, naming the
  loop ``traffic/<loop>.py`` that reads them;
* ``metrics/<metric>.py``     -- the reducer of one per-layer metric;
* ``work/<family>.py``        -- least bytes and flops of a kernel family;
* ``peaks.json``              -- published peaks keyed by ``device_kind``.

The yardstick (data generators, references, trace reduction, peaks) lives
here and imports nothing of the program under ``src/``; only the traffic
loops call the program's entry points.
"""
