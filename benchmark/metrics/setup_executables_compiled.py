"""Compile caches (compile_cache.py): how many of the programs lowered in
set-up had their executable compiled and written to the persistent cache
(the records' ``executable == "compiled"``) rather than read from it: 0 in
a warm run, at least 1 on a checkout's first run or when another tree's
executables evicted this one's."""

from benchmark.metrics import _setup


def read(facts):
    recs = _setup.records(facts)
    if recs is None:
        return None
    return sum(r["executable"] == "compiled" for r in recs)
