"""bag_roofline.dcn: Share of the embedding-bag kernels' roofline in the
DLRM-DCNv2 cell: their bytes (valid rows, each table's P_t index slots,
pooled vectors) at peak HBM bandwidth over their device time."""
from bench import layers


def read(reading):
    return layers.bag_roofline(reading)
