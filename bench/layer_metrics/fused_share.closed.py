"""Engine: share of the window's device batches that the fused program
served, in % (the program's ``engine_device_batches_total{path}``: the
``path=fused`` count over every path's).  A program without the family
reads as None."""
from bench.harness import counter_total


def read(run):
    fam = run.counters.get("engine_device_batches_total")
    total = counter_total(run, "engine_device_batches_total")
    if not total:
        return None
    return 100.0 * fam["values"].get("path=fused", 0) / total
