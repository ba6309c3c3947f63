"""Load generator: a client's own median time from an answer received to
its next request sent (parsing the answer, building the next body). A
closed loop offers less load when this grows."""
from harness.stats import median


def read(view):
    gaps = view["client"]["between_s"]
    return median(gaps) * 1e3 if gaps else None
