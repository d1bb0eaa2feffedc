"""Step lowering (executor.py, parallel/parallel_executor.py): median over
the traced steps of the ``bm/train_step`` span's length less the
``pt/*/fetch_sync`` time inside it (``AsyncDispatchQueue._sync_oldest``):
what the host spends itself on a step — transfers, the PRNG key, the
dispatch, the bookkeeping.  The step time at which the host becomes the
limit."""

from benchmark.metrics import _scopes


def read(facts):
    got = _scopes.reading(facts)
    return got["host"][0] * 1e3 if got and got["host"] else None
