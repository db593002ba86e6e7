"""95th percentile of TTFT over the requests due in the window, as
``stats.ttft_s`` takes it: the tail of the queue for a slot."""
from bench import stats


def read(run):
    ttft = stats.ttft_s(run.rec)
    return 1e3 * stats.percentile(ttft, 95) if ttft else None
