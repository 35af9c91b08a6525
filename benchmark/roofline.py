"""The operations and bytes a kernel's work asks for, and the time the
chip's peaks (``peaks.json``) allow it: what ``<kernel>_roofline`` metrics
are shares of.  The work asked, whatever implements it: no factor for the
passes of a multi-pass matmul, no padding."""
import json
import os


def knn_flat_flops(queries: float, rows: float, dim: int) -> float:
    """Exact FLAT KNN: one multiply-add a query, row and dimension."""
    return 2.0 * queries * rows * dim


def knn_flat_bytes(queries: float, rows: float, dim: int, k: int) -> float:
    """One pass over the float32 bank and its bias plane, the queries in,
    k (distance, id) pairs a query out."""
    return rows * dim * 4.0 + rows * 4.0 + queries * dim * 4.0 + queries * k * 8.0


def seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline time of one call: the slower of compute and memory."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def the_peaks():
    """The peaks of the one device kind ``peaks.json`` lists (a reader is
    not told the kind; with several kinds listed there is no telling)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as fh:
        kinds = [v for v in json.load(fh).values() if isinstance(v, dict)]
    return kinds[0] if len(kinds) == 1 else None
