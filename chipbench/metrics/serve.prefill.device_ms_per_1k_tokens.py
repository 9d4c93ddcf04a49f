"""Device busy time of the operations issued inside the program's
``serve.prefill`` spans in the traced window (the prompt's layer scan,
its cache scatter and first token), per 1,000 prompt tokens (the spans'
``tokens``)."""
from chipbench import program
from chipbench import trace as tr


def read(outcome, run):
    spans = program.spans(outcome, "serve.prefill")
    tokens = sum(attrs["tokens"] for *_, attrs in spans or ())
    if not tokens:
        return None
    dev = tr.devices(outcome.trace)[0]
    busy = tr.busy_in_spans(outcome.trace, dev,
                            [(a, b) for _, a, b, _ in spans])
    return 1e3 * sum(busy) / (tokens / 1e3)
