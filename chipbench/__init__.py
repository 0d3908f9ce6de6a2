"""Chip benchmark of the XQuery serving path.

One cell (a deployment of ``configs/`` under a traffic mix of
``traffic/``) runs per process: ``python3 chipbench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>``. Everything that
decides a number lives here: data and traffic drawn from the seed, the
plain numpy reference that decides ``correct``, the trace reduction,
the peak table and one reader per metric (``metrics/``).
"""
