"""The benchmark: the yardstick every later PR is measured with.

``BENCHMARK.json`` at the root of the repo declares the cells and the
metrics; everything else is found from it by name:

    run.py                      one cell, once:  --workload --seed --seconds --trace
    harness/                    what is the same for every cell
    configs/<config>.json       a configuration's sizes, source and layout
    traffic/<mix>.json          a traffic mix: parameters of the one generator
    families/<family>.py        a model family: FLOPs and bytes from shapes,
                                and its plain float32 reference
    drivers/<driver>.py         how one kind of job is built through the
                                program's normal entry points and run
    layer_metrics/<metric>.py   one per-layer metric: ``read(obs)``
    peaks/<kind>.json           a device kind's published peaks
    testdata/                   small recorded traces the reduction is
                                checked on

A later PR adds a cell, a configuration, a traffic mix or a per-layer
metric by adding files here and one entry to ``BENCHMARK.json``; it
edits no file that is there.  From the program (``paddle_tpu``) the
benchmark takes the system under test, its counters and its kernel
names, and nothing it could compute itself.
"""
