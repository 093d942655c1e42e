"""The benchmark: ``python3 bench/run.py --workload <cell> ...`` runs one
cell of ``BENCHMARK.json``. Its modules are imported as ``bench.<name>``
so that none of them shadows a module of the same name elsewhere."""
