"""mfu.dcn: Model FLOPs of the rows served over the time inside
serve(), as a share of peak bf16 FLOP/s: the family's dense tower per
row and one D-wide add per valid bag slot, as ``layers.mfu`` counts
them; D is read from the configuration's ``embedding_dim`` (MLPerf's
flag), which ``flops.pooling_flops`` does not know."""
from bench.families import family


def read(reading):
    if reading.serve_s <= 0 or reading.rows <= 0:
        return None
    cfg = reading.cell.config
    fl = (reading.rows * sum(family(cfg).dense_flops_per_row(cfg).values())
          + float(reading.valid_slots) * cfg["embedding_dim"])
    return 100.0 * fl / reading.serve_s / reading.peak["bf16_flops_per_s"]
