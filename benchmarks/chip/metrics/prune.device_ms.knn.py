"""Device ms per answered query in the cascade's programs: probe, WCD,
RWMD min-cdist and bounds, the survivor keep, seed top-k, threshold."""
from knnstats import PRUNE, per_query_ms


def read(run):
    return per_query_ms(run, PRUNE)
