"""Device idle time inside the program's ``serve.decode`` spans in the
traced window, per decode: the host's work around each decode program
(inputs, dispatch, the token sync, the per-slot loop)."""
from chipbench import program


def read(outcome, run):
    spans = program.spans(outcome, "serve.decode")
    if not spans:
        return None
    return 1e3 * program.idle_seconds(outcome.trace, spans) / len(spans)
