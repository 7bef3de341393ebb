"""A percentile of the load generator's own records: ``ttft`` (first
token minus due), ``itl`` (gaps between streamed tokens), ``late`` (sent
minus due), ``front_overhead`` (client TTFT minus the engine's own), in
ms."""
from harness import quantile


def read(ctx, field, p):
    xs = ctx.get("clients", {}).get(field)
    return quantile(xs, p / 100.0) if xs else None
