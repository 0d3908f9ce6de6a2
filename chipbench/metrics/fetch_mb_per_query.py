"""Megabytes copied device-to-host per request: the window's
``fetch_bytes`` counter of ``ServiceStats`` over its requests."""
from chipbench import stages


def read(ctx):
    return stages.per_query_mb(ctx, "fetch_bytes")
