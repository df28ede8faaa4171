"""The chip benchmark of scda checkpoints under a training job.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on.  Everything that measures (traffic, reference, comparison,
trace reduction, FLOP counts, peak table) lives in this directory; from
the program it takes only the system under test and its spans.
"""
