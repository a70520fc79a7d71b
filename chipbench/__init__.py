"""chipbench: the chip benchmark of deepspeed_tpu (see README.md here).

One cell, one process, one JSON line:
``python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.
"""
