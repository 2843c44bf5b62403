"""chipbench — the repo's chip benchmark (see README.md beside this file).

One cell, one run, one process: ``python3 -m chipbench.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.  Everything a later PR adds is a
new file found by the name ``BENCHMARK.json`` gives it.
"""
