"""99th percentile of the gaps between consecutive tokens of the
window's requests (a prefill stall lands in these gaps)."""
from chipbench import stats


def read(outcome, run):
    gaps = stats.token_gaps(outcome.observed["logs"])
    return 1e3 * stats.percentile(gaps, 99) if gaps else None
