"""On-chip benchmark of MobileRAG's served path (see PERF.md).

`python rag_bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of BENCHMARK.json once on the accelerator it
is started on and prints one JSON result line last. Configurations,
traffic mixes, per-cell rates and limits, and per-layer metric readers
are data files under this directory, found by the names BENCHMARK.json
gives them.
"""
