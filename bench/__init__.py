"""On-chip benchmark of InferLine's serving path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything that belongs to one
configuration, traffic mix or per-layer metric is a file of its own,
found by the name that ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``      model sizes, served configuration,
                                       correctness limit, model family
* ``bench/families/<family>.py``       what depends on a model's shape:
                                       building it from the registry,
                                       its plain reference, its counts
                                       of operations and bytes
* ``bench/traffic/<traffic>.json``     one traffic mix (parameters only)
* ``bench/traffic/kinds/<kind>.py``    a general arrival generator
* ``bench/metrics/<metric>.py``        the reader of one per-layer metric

The yardstick lives here too: traffic generation, the end-to-end
arithmetic (:mod:`bench.stats`), the trace reduction (:mod:`bench.trace`),
the chip's peaks (``bench/peaks.json``), the roofline
(:mod:`bench.flops`), the seeded weights (:mod:`bench.weights`), and
the comparison with each family's plain reference that decides
``correct`` (:mod:`bench.reference`).
"""
