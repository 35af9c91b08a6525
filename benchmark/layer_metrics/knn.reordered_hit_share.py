"""Search layer: the share of the window's KNN hits whose place in their
reply changed when each query's hits were put in the order of their
canonical scores (``rtpu_knn_hits_reordered_total`` against
``rtpu_knn_hits_total``, after minus before): 100 x reordered / hits.  The
scoring path ranks by its own float32 distances; two neighbours a few ulps
apart can come out of it in the other order.  None on a program without
the two series, or a window without a hit."""
from benchmark import counters


def read(obs):
    hits = counters.delta(obs, "rtpu_knn_hits_total")
    moved = counters.delta(obs, "rtpu_knn_hits_reordered_total")
    if moved is None or not hits:
        return None
    return 100.0 * moved / hits
