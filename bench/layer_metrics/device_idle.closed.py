"""Device: share of the traced window in which no program ran, in %."""
from bench.harness import device_idle_percent


def read(run):
    return device_idle_percent(run)
