"""Coalescer layer: the share of the query slots the window's KNN dispatches
scored that no search asked for (``rtpu_knn_query_slots_total`` against
``rtpu_knn_queries_total``, after minus before): 100 x (slots - queries) /
slots.  A stacked dispatch pads its queries to one of a few bucket sizes, so
that no frame meets a cold program; this is what the buckets waste.  None on
a program without the two series, or a window without a dispatch."""
from benchmark import counters


def read(obs):
    slots = counters.delta(obs, "rtpu_knn_query_slots_total")
    queries = counters.delta(obs, "rtpu_knn_queries_total")
    if queries is None or not slots:
        return None
    return 100.0 * (slots - queries) / slots
