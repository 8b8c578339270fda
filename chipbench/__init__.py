"""On-chip benchmark of the federated round (see ``BENCHMARK.json``).

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one task kind, one traffic
mix, one per-layer metric or one layer's device programs lives in a file
of its own, found by name: ``configs/<config>.json``,
``tasks/<kind>.py``, ``workloads/<cell>.json``, ``metrics/<metric>.py``,
``programs/<layer>/<program>.txt``.
"""
