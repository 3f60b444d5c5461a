"""bookkeeping_idle_ms.dcn: Milliseconds per batch with no operation on the
device inside the repro.assemble, account, clock, complete and stats
spans (the served path's bookkeeping on the host)."""
from bench import layers


def read(reading):
    return layers.span_idle_ms(reading, "bookkeeping_idle_ms")
