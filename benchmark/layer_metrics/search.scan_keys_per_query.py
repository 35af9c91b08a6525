"""Search layer: keys a keyspace scan walked on behalf of a search, a KNN
query of the window (``rtpu_search_scan_keys_total`` over
``rtpu_knn_queries_total``, after minus before).  0 where the index learns
of a write at the write; the size of the keyspace where every search
walks it.  None on a program without the two series, or a window without a
query."""
from benchmark import counters


def read(obs):
    keys = counters.delta(obs, "rtpu_search_scan_keys_total")
    queries = counters.delta(obs, "rtpu_knn_queries_total")
    if keys is None or not queries:
        return None
    return keys / queries
