"""gather_idle_ms.dcn: Milliseconds per batch with no operation on the
device inside the repro.gather and repro.dense spans (the blocking fetch of
pooled vectors, and the dense step's upload and score fetch)."""
from bench import layers


def read(reading):
    return layers.span_idle_ms(reading, "gather_idle_ms")
