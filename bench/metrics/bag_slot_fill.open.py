"""bag_slot_fill.open: Valid bag slots over the slots the bag kernels
walked in the window (the engine's bag_slots_valid and bag_slots)."""
from bench import layers


def read(reading):
    return layers.bag_slot_fill(reading)
