"""Load generator (benchmark/generators): 95th percentile of how late a
request was sent after it was due, on the benchmark's clock — a starved
generator must not read as a fast server."""

from benchmark import stats


def read(facts):
    late = facts.get("generator_late_s")
    return stats.percentile(late, 95) * 1e3 if late else None
