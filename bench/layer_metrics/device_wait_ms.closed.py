"""Device, as the host sees it: mean time per batch, in ms, in the tier
device calls (``enqueue``) and the blocking copy of their results
(``sync``), from the program's ``engine_stage_ms``."""
from bench.stages import mean_ms


def read(run):
    return mean_ms(run, "engine_stage_ms", ("enqueue", "sync"), per="map")
