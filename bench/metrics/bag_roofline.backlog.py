"""bag_roofline.backlog: Share of the embedding-bag kernels' roofline:
their bytes at peak HBM bandwidth over their device time."""
from bench import layers


def read(reading):
    return layers.bag_roofline(reading)
