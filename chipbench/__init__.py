"""The chip benchmark: one command runs one cell of ``BENCHMARK.json`` once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name in this tree: a configuration in
``configs/<name>.json`` with its plain reference in ``refs/<arch>.py``, a
traffic mix in ``traffic/<name>.json`` driven by ``kinds/<kind>.py``, and a
per-layer metric's reader in ``metrics/<name>.py``.
"""
