"""perfbench: the benchmark of fedtpu (BENCHMARK.json's only path).

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: the seeded data generators, the plain reference, the
clock and the arithmetic on its stamps, the trace reduction, the table of
peaks and the operation counts. From the program it takes the system under
test (``fedtpu.orchestration.loop.run_experiment``), the line it prints for
every round (stamped by the harness's clock), its events sink and the names
of its device operations. One cell, one run:

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
