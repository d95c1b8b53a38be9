"""Public op: the Pallas chunkwise mLSTM scan when the sequence is a whole
number of chunks, the plain reference otherwise.
"""

from __future__ import annotations

from .ref import mlstm_scan_ref
from .scan import mlstm_scan


def mlstm_scan_op(q, k, v, ig, lf, *, chunk: int = 64):
    if q.shape[2] % chunk == 0:
        return mlstm_scan(q, k, v, ig, lf, chunk=chunk)
    return mlstm_scan_ref(q, k, v, ig, lf)


__all__ = ["mlstm_scan_op", "mlstm_scan", "mlstm_scan_ref"]
