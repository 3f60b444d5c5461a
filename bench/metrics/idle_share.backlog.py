"""idle_share.backlog: Share of the traced window with no operation on the
device."""
from bench import layers


def read(reading):
    return layers.idle_share(reading)
