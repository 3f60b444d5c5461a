"""bag_walk_fill.dcn: Valid bag slots over the slots the bag kernels walk
under each table's own bag length, over the window (the engine's
bag_slots_valid and bag_slots_walked counters); None where the program
keeps no bag_slots_walked counter."""


def read(reading):
    walked = reading.counters.get("bag_slots_walked", 0)
    if walked <= 0:
        return None
    return 100.0 * reading.counters["bag_slots_valid"] / walked
