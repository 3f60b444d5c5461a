"""dense_roofline.dcn: Share of the dense step's roofline in the
DLRM-DCNv2 cell (bottom MLP, three low-rank cross layers, top MLP):
max(FLOPs at peak, bytes at peak bandwidth) over its device time."""
from bench import layers


def read(reading):
    return layers.dense_roofline(reading)
