"""Device idle time inside the program's ``serve.prefill`` spans (one per
prefilled request) in the traced window, per request: the host's share
of a prefill, which every active request waits through."""
from chipbench import program


def read(outcome, run):
    spans = program.spans(outcome, "serve.prefill")
    if not spans:
        return None
    return 1e3 * program.idle_seconds(outcome.trace, spans) / len(spans)
