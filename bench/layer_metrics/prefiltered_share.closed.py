"""Engine: share of the window's queries the prefilters decided, in %
(the program's ``engine_prefiltered_total`` over ``engine_queries_total``)."""
from bench.harness import counter_total


def read(run):
    total = counter_total(run, "engine_queries_total")
    if not total:
        return None
    return 100.0 * counter_total(run, "engine_prefiltered_total") / total
