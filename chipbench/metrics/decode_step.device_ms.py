"""Device busy time per engine step that only decoded (no prefill in
it), in the traced part of the window."""
from chipbench import readers


def read(outcome, run):
    steps = readers.decode_only(outcome)
    if not steps:
        return None
    return 1e3 * sum(s for _, s in steps) / len(steps)
