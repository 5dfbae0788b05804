"""Result comparison for differential checks (engine vs oracle).

The ONE place a ``collect()`` dict is compared with another, shared by
the test suite and ``chip_smoke.py``, so both hold the engines to the
same tolerance.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def assert_results_equal(a: Dict[str, Any], b: Dict[str, Any],
                         rtol: float = 5e-3, atol: float = 1e-6,
                         ordered: bool = True, msg: str = "") -> None:
    """Compare two collect() dicts.

    Columns pass through ``np.atleast_1d(np.asarray(...))`` so 0-d
    scalars (scalar aggregates like q6/q14, or values that went through
    a float constructor) never reach ``np.sort(axis=-1)``.  String
    columns compare exactly; numeric ones within ``rtol``/``atol``
    (sorted first when ``ordered=False``).
    """
    a = {k: np.atleast_1d(np.asarray(v)) for k, v in a.items()}
    b = {k: np.atleast_1d(np.asarray(v)) for k, v in b.items()}
    assert set(a) == set(b), msg
    for k in a:
        x, y = a[k], b[k]
        assert x.shape == y.shape, (msg, k, x.shape, y.shape)
        if x.dtype == object or y.dtype == object:
            if ordered:
                assert list(x) == list(y), (msg, k)
            else:
                assert sorted(x) == sorted(y), (msg, k)
        else:
            xf = np.atleast_1d(np.asarray(x, dtype=np.float64))
            yf = np.atleast_1d(np.asarray(y, dtype=np.float64))
            if not ordered:
                xf, yf = np.sort(xf), np.sort(yf)
            np.testing.assert_allclose(xf, yf, rtol=rtol, atol=atol,
                                       err_msg=f"{msg}/{k}")
