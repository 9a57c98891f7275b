"""On-chip benchmark harness of VELOC-JAX.

Everything a cell needs is found by name under the benchmark's root:
``configs/<config>.json`` (sizes, with the plain reference named in it),
``traffic/<traffic>.json`` (the workload's parameters, read by the loop it
names under ``loops/``), ``limits/<workload>.json`` (the correctness
limits of the cell) and ``metrics/<metric>.py`` (one reader per per-layer
metric).  A cell or metric is added by adding files.
"""
