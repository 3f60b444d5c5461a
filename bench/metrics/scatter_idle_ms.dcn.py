"""scatter_idle_ms.dcn: Milliseconds per batch with no operation on the
device inside the repro.route and repro.scatter spans (routing, index slicing,
uploads and bag dispatch)."""
from bench import layers


def read(reading):
    return layers.span_idle_ms(reading, "scatter_idle_ms")
