"""Daemon: mean event-loop work per tick, in ms: the ``collect`` (first
request to the hand-off) and ``resolve`` (futures, latency observations)
stages of the program's ``daemon_stage_ms``."""
from bench.stages import mean_ms


def read(run):
    return mean_ms(run, "daemon_stage_ms", ("collect", "resolve"), per="collect")
