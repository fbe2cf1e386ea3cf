"""Chip benchmark for the continuous-batching server.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU and
prints one JSON line. Configurations (``configs/<name>.json``), traffic
mixes (``traffic/<name>.json``) and per-layer metric readers
(``metrics/<name>.py``) are found by the names ``BENCHMARK.json`` gives.
"""
