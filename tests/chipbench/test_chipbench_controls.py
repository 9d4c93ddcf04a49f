"""The chat cell's control comes out as not correct: the plain
reference, computed a step below the precision the configuration states
(float8 for bfloat16), in the program's place, read by the cell's own
number against the cell's own limit.  On the chip the control was read
at the cell's size (see PERF.md); here it runs at a reduced size a test
run can hold."""
import numpy as np

import cb_rehearsal as R
from chipbench import bench
from chipbench.reference import qwen3


def _limit(cell, name):
    return bench.load_json(bench.HERE / "checks" / f"{cell}.json")[name][
        "limit"]


def test_serve_control_float8_is_incorrect():
    a = R.reduced_config("qwen3-4b")["arch"]
    rng = np.random.default_rng(1)
    sample = [(rng.integers(0, a["vocab_size"], 48).astype(np.int32),
               rng.integers(0, a["vocab_size"], 24).astype(np.int32))
              for _ in range(12)]
    gaps = qwen3.control_gaps(a, 3, sample, pad_to=72)
    assert max(gaps) > _limit("qwen3-4b.chat", "max_logit_gap")

