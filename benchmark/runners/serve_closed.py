"""Closed loop: ``clients`` callers, each waiting for its reply before it
sends again — batch prediction steps, evaluation and synthetic-data jobs."""

from benchmark.runners import serve_common


def run(ctx):
    return serve_common.run(ctx, "closed")
