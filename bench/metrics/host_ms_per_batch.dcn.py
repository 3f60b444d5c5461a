"""host_ms_per_batch.dcn: Host milliseconds per batch inside serve()
calls with no operation on the device."""
from bench import layers


def read(reading):
    return layers.host_ms_per_batch(reading)
