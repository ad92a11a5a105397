"""The chip benchmark: one command runs one cell of BENCHMARK.json once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration under
``configs/``, its traffic mix under ``traffic/`` (which names the window
driver under ``drivers/``), its correctness limits under ``limits/``, and
one reader per metric under ``e2e/`` and ``layers/``.  The yardstick
(data generator, references, work counts, peaks, trace reduction) lives
here too, apart from the program under test in ``src/``.
"""
