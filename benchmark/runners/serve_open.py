"""Open loop: arrivals on a seeded schedule at a rate fixed in the traffic
file, whatever the server does — independent users."""

from benchmark.runners import serve_common


def run(ctx):
    return serve_common.run(ctx, "open")
