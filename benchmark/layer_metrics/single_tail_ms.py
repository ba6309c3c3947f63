"""Entry points: the 95th percentile of the latencies of the operations
that are one query (all but ``query_many``), whose mean is the end-to-end
``single_mean_ms``. It sits among some fifty samples a run, the answers of
10^4 rows and more, and how many rows those hold changes with the seed
(the boxes fall on other clusters), so it spreads two to three times as
widely as the mean from seed to seed: it is reported here and carries no
bound (PERF.md, section 2)."""
from harness.stats import percentile


def read(view):
    single = view["client"].get("single_ms")
    return percentile(single, 95.0) if single else None
