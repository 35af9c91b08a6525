"""Kernels layer: the share of the rows the filter's bytes kernels were handed
for point commands during the window that no command asked for (METRICS
``rtpu_point_rows_issued_total`` and ``rtpu_point_rows_valid_total``, after
minus before): 100 x (issued - valid) / issued — ``kernel.padded_row_share``'s
rule at the single filter's dispatches.  One row in a bucket of 256 reads
99.6; a fuller dispatch lowers it.  None on a program without the two
series, or a window that issued no row."""
from benchmark import counters


def read(obs):
    issued = counters.delta(obs, "rtpu_point_rows_issued_total")
    valid = counters.delta(obs, "rtpu_point_rows_valid_total")
    if issued is None or valid is None or issued <= 0:
        return None
    return 100.0 * (issued - valid) / issued
