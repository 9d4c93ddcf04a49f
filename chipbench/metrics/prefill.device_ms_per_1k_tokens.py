"""Device time a prefill adds, per 1,000 prompt tokens: the busy time of
each traced step that held a prefill, less the mean busy time of a
decode-only step, summed and divided by the prompt tokens prefilled."""
from chipbench import readers


def read(outcome, run):
    decode = readers.decode_only(outcome)
    pre = [(st, s) for st, s in readers.traced_steps(outcome)
           if st["prefill"]]
    if not decode or not pre:
        return None
    base = sum(s for _, s in decode) / len(decode)
    tokens = sum(sum(st["prompts"]) for st, _ in pre)
    return 1e3 * sum(s - base for _, s in pre) / (tokens / 1e3)
