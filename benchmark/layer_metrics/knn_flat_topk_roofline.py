"""Kernels layer: the FLAT KNN program's share of its roofline over the
traced slice — the time the chip's peaks allow the work the slice's searches
asked for (``benchmark/roofline.py``: one pass over the bank a stacked
dispatch, 2 Q N d operations; the work asked, whatever implements it) over
the device time of the program ``knn_flat_topk`` (``reduce_trace.py``'s
per-program time).

Dispatches in the slice: the slice's verified searches over the queries a
stacked dispatch carried (``members`` of the slice's ``kernel`` spans of
``FT.SEARCH``: one query a member in this mix).  Rows: what the server's
counters say a query scored (``rtpu_knn_rows_scored_total`` over
``rtpu_knn_queries_total``, window's deltas).  k and dim: the traffic's.
None where any of that is absent: a program without the spans or counters,
an untraced run."""
from benchmark import counters, roofline

PROGRAM = "knn_flat_topk"


def read(obs):
    if not obs.device or not obs.slice_ops:
        return None
    device_s = sum(s for name, s in obs.device.get("programs") or ()
                   if PROGRAM in name)
    members = [int(s["attrs"].get("members", 1)) for f in obs.frames
               for s in f["spans"]
               if s["name"] == "kernel" and s["attrs"].get("verb") == "FT.SEARCH"]
    queries = counters.delta(obs, "rtpu_knn_queries_total")
    scored = counters.delta(obs, "rtpu_knn_rows_scored_total")
    peaks = roofline.the_peaks()
    if not device_s or not members or not queries or not scored or peaks is None:
        return None
    per_call = sum(members) / len(members)
    rows = scored / queries
    dim, k = obs.params["dim"], obs.params["k"]
    call_s = roofline.seconds(roofline.knn_flat_flops(per_call, rows, dim),
                              roofline.knn_flat_bytes(per_call, rows, dim, k), peaks)
    return 100.0 * (obs.slice_ops / per_call) * call_s / device_s
