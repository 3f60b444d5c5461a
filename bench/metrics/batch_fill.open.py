"""batch_fill.open: Real rows over the rows of the batches the ingress
batcher formed (engine.batches_seen x batch size)."""
from bench import layers


def read(reading):
    return layers.batch_fill(reading)
