"""idle_share.dcn: Share of the traced window with no operation on the
device."""
from bench import layers


def read(reading):
    return layers.idle_share(reading)
