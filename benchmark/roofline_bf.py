"""The bytes a single-item bloom command asks of the chip, beside
``roofline.py`` (which holds the peaks' reader and the KNN's counts): what
``bloom_point_roofline`` is a share of.  The work asked, whatever implements
it: no padding to a bucket, no access granule, no second pass."""


def bloom_point_bytes(exists: float, adds: float, k: int, item_bytes: float) -> float:
    """One byte a cell of the plane: a probe reads its k cells, an add reads
    and writes them; either brings its item's bytes in and takes one byte
    (the flag) out."""
    return exists * k + adds * 2 * k + (exists + adds) * (item_bytes + 1.0)


def mean_item_bytes(prefix: str, key_max: int) -> float:
    """Mean byte length of ``<prefix><n>`` for n uniform in 1..key_max."""
    digits, total, lo = 1, 0, 1
    while lo <= key_max:
        hi = min(key_max, lo * 10 - 1)
        total += (hi - lo + 1) * digits
        digits, lo = digits + 1, lo * 10
    return len(prefix.encode()) + total / key_max
