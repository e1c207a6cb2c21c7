"""The tpu-bfs benchmark: data-driven cells run one at a time.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout root names the cells, configurations
and metrics; each of them is a file of its own under this directory,
found by its name (``configs/<config>.json``, ``workloads/<cell>.json``,
``traffic/<kind>.py``, ``metrics/<metric>.py``). The graph generator,
the plain reference, the trace reduction and the roofline model live
here too, so the yardstick does not move with the program under test.
"""
