"""p50_ms.open: The 50th percentile (nearest rank) of the latencies of
all requests of the window, from scheduled arrival to the return of their
serve() call."""
from bench import layers


def read(reading):
    return layers.latency_ms(reading, 0.5)
