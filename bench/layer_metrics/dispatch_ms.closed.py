"""Daemon: mean wall time of one padded dispatch over the window, in ms
(the program's ``daemon_dispatch_ms`` histogram, sum over count)."""
from bench.harness import histogram_mean


def read(run):
    return histogram_mean(run, "daemon_dispatch_ms")
