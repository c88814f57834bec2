"""Daemon: mean executor round trip per tick, in ms: the loop's dispatch
time less the worker's own (``daemon_stage_ms{stage=handoff}``)."""
from bench.stages import mean_ms


def read(run):
    return mean_ms(run, "daemon_stage_ms", ("handoff",), per="handoff")
