"""bag_slot_fill.dcn: Valid bag slots over the slots of the bag calls'
index blocks in the window (the engine's bag_slots_valid and bag_slots);
bag_walk_fill.dcn is the share of the slots the kernels walk."""
from bench import layers


def read(reading):
    return layers.bag_slot_fill(reading)
