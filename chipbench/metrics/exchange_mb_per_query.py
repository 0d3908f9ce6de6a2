"""Logical megabytes the plans' collectives moved per request: the
window's ``exchange_bytes`` counter of ``ServiceStats`` (a b-byte
operand over P partitions counts b*(P-1), fixed when the plan is
traced) over its requests."""
from chipbench import stages


def read(ctx):
    return stages.per_query_mb(ctx, "exchange_bytes")
