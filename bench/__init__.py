"""On-chip benchmark of the chunked-prefill serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.  Everything
that measures (traffic generation, the reduction from spans, counters and the
device trace to metrics, the table of peaks, the FLOP count and the float32
reference that decides ``correct``) lives in this directory; from the program
under test (``src/repro``) the benchmark takes only the serving loop itself.
"""
