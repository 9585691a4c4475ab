"""On-chip benchmark of the PFP system: one command runs one cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/workloads/<cell>.json``  the cell: config, traffic kind and its
  parameters, chips, why;
* ``bench/configs/<config>.json``  the configuration as it is run, with the
  system module that builds it and the reference that checks it;
* ``bench/systems/<system>.py``    builds the system under test from a config;
* ``bench/traffic/<kind>.py``      one driver per traffic kind;
* ``bench/metrics/<metric>.py``    one reducer per per-layer metric;
* ``bench/reference/<name>.py``    the plain float32 references;
* ``bench/costs/``, ``bench/peaks.json``  analytic operation counts and the
  chip's published peaks.
"""
