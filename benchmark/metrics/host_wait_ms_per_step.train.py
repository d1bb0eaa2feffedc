"""Step lowering (executor.py, parallel/parallel_executor.py): median over
the traced steps of the ``pt/*/fetch_sync`` time inside a
``bm/train_step`` span: the host blocked on the bounded dispatch window
(``AsyncDispatchQueue._sync_oldest``), waiting for the device."""

from benchmark.metrics import _scopes


def read(facts):
    got = _scopes.reading(facts)
    return got["host"][1] * 1e3 if got and got["host"] else None
