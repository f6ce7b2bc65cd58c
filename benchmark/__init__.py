"""The on-chip benchmark of deeplearning4j-tpu (see README.md in this
directory). One cell, one run: ``python3 benchmark/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``."""
