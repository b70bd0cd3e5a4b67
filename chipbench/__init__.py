"""chipbench: the repo's benchmark. One command runs one cell once:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one cell, one family or one
per-layer metric is a file of its own, found by its name in BENCHMARK.json:
``configs/<configuration>.json``, ``workloads/<cell>.json``,
``families/<family>.py``, ``metrics/<metric>.py``. A later PR adds files and
entries and edits none that is here. PERF.md says what each number means.
"""
