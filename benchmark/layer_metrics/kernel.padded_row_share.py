"""Kernels layer: the share of the rows the bank kernels walked on the device
during the window that no caller sent (METRICS
``rtpu_kernel_rows_issued_total`` and ``rtpu_kernel_rows_valid_total``, after
minus before): 100 x (issued - valid) / issued.  A bucket pads a flush to a
static shape; what the device does for the padding is time nobody asked
for.  None on a program without the two series, or a window that issued no
row."""
from benchmark import counters


def read(obs):
    issued = counters.delta(obs, "rtpu_kernel_rows_issued_total")
    valid = counters.delta(obs, "rtpu_kernel_rows_valid_total")
    if issued is None or valid is None or issued <= 0:
        return None
    return 100.0 * (issued - valid) / issued
