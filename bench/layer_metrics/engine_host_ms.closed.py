"""Engine: mean host work per batch, in ms: the ``map``, ``prefilter``,
``plan`` and ``scatter`` stages of the program's ``engine_stage_ms``."""
from bench.stages import mean_ms


def read(run):
    return mean_ms(run, "engine_stage_ms", ("map", "prefilter", "plan", "scatter"), per="map")
