"""On-chip serving benchmark of the repository's continuous scheduler.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything that makes up a
cell is data found by name: ``configs/<config>.json`` (model sizes and the
output check), ``traffic/<traffic>.json`` (arrivals, lengths, sampler
settings) and ``metrics/<metric>.py`` (one reader per per-layer metric).
"""
