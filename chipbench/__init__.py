"""On-chip benchmark of the MARVEL serving path.

Run one cell from the repository root::

    python3 -m chipbench.run --workload resnet50-224.closed --seed 7 \
        --seconds 10 --trace 0

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration (``configs/<config>.json``), traffic mix
(``traffic/<traffic>.json``) and per-layer metrics (``metrics/<name>.py``);
``workloads/<cell>.json`` holds the cell's correctness limits.  See
``PERF.md`` at the repository root for what each number means.
"""
