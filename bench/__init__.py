"""Chip benchmark of the joint-sparse serving engine.

Everything a measurement depends on lives here and not in the program:
traffic generation, weights made from the seed, the plain reference and
the comparison that decides ``correct``, the table of peaks, the work
each kernel call needs, and the reduction of a profiler trace to
metrics. ``python bench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>`` runs one cell once; ``BENCHMARK.json`` at the root
of the checkout names the cells and metrics.
"""
