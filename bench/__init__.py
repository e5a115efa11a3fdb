"""On-chip benchmark of the repro serving and training paths.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything that measures (traffic,
weights, the float32 reference, work counts, peaks, trace reduction) lives
here; the program under ``src/`` is only the system under test.
"""
