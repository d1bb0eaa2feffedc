"""Op kernels (ops/sparse_select.py): device milliseconds per traced step
of the indexer's scores and the top-k key selection — the Fluid op types
``indexer_score`` and ``select_topk_keys`` (neither has a gradient: the
indexer is frozen and a selection is a set).  The indexer's three
projections are ``mul`` ops and count under ``device_ms_per_step.matmul``."""

from benchmark.metrics import _types


def read(facts):
    s = _types.seconds_per_step(facts, ("indexer_score", "select_topk_keys"))
    return None if s is None else s * 1e3
