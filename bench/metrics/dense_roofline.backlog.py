"""dense_roofline.backlog: Share of the dense step's roofline: max(FLOPs at
peak, bytes at peak bandwidth) over its device time."""
from bench import layers


def read(reading):
    return layers.dense_roofline(reading)
