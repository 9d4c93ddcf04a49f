"""Model FLOP/s utilisation of the whole engine step over the window:
the operations of every token prefilled and decoded (``flops``) over the
host time spent inside ``step()`` times the chip's peak."""
from chipbench import flops, readers


def read(outcome, run):
    steps = readers.window_steps(outcome)
    held = sum(st["t1"] - st["t0"] for st in steps)
    if not steps or held <= 0:
        return None
    a = run.config["arch"]
    work = sum(flops.decode_flops(a, st["contexts"])
               + sum(flops.prefill_flops(a, n) for n in st["prompts"])
               for st in steps)
    return 100.0 * work / (held * run.peaks["bf16_flops_per_s"])
