"""KV cache (serving/kv_cache.py): share of the full prompt pages that
were aliased from the prefix cache rather than written, from
``ServingMetrics.paged_snapshot()`` over the fill and the window."""


def read(facts):
    hits, misses = facts.get("prefix_hits"), facts.get("prefix_misses")
    if hits is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
