"""``tools/measure.py``: the spread bounds are set from is the contract's —
the distance between the quartiles of ``statistics.quantiles(values, n=4)``
over the median (numpy's quartiles lie closer together)."""
import importlib.util
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def measure():
    spec = importlib.util.spec_from_file_location(
        "measure", os.path.join(BENCH, "tools", "measure.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spread_is_the_exclusive_quartiles_over_the_median():
    m = measure()
    six = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]  # quartiles at 10.75 and 14.25, median 12.5
    assert m.spread(six) == pytest.approx(3.5 / 12.5)
    q1, q3 = np.percentile(six, [25, 75])
    assert m.spread(six) > (q3 - q1) / 12.5  # numpy's would read 2.5 / 12.5
    assert np.isnan(m.spread([3.0]))
