"""mfu.open: Model FLOPs of the rows served over the time inside serve(),
as a share of peak bf16 FLOP/s."""
from bench import layers


def read(reading):
    return layers.mfu(reading)
