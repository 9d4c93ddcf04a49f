"""Share of the roofline of the decode-only steps in the traced window:
the least time the chip could take for them (the larger of their
operations over peak FLOP/s and their bytes over HBM bandwidth, from
``flops.decode_flops`` and ``flops.decode_bytes`` at each step's active
slots and valid cache lengths) over their device busy time."""
from chipbench import flops, readers


def read(outcome, run):
    steps = readers.decode_only(outcome)
    busy = sum(s for _, s in steps)
    if not steps or busy <= 0:
        return None
    a, p = run.config["arch"], run.peaks
    ideal = sum(max(flops.decode_flops(a, st["contexts"])
                    / p["bf16_flops_per_s"],
                    flops.decode_bytes(a, st["contexts"])
                    / p["hbm_bytes_per_s"]) for st, _ in steps)
    return 100.0 * ideal / busy
