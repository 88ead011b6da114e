"""The on-chip benchmark of the edge aggregator: ``python3 bench/run.py
--workload <cell> --seed <n> --seconds <s> --trace <0|1>`` runs one cell
of ``BENCHMARK.json`` (see ``harness``)."""
