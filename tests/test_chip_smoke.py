"""``chip_smoke.py`` off the chip: it must refuse a host without a TPU,
and its serve and train phases, with their checks, must pass on reduced
configs (the kernel phase asserts a Mosaic kernel, which only a TPU
compile emits; ``tests/test_tpu_compile.py`` covers that part)."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_without_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert out.splitlines()[-1].startswith("device: platform=cpu")


def test_serve_phase_reduced(capsys):
    chip_smoke.serve_phase(get_config("qwen3-4b").reduced(), 0, slots=2,
                           max_len=64, n_requests=3, prompt_len=(4, 20),
                           new_tokens=(2, 6))
    assert "== forward_argmax" in capsys.readouterr().out


def test_train_phase_reduced(capsys):
    chip_smoke.train_phase(get_config("mamba2-130m").reduced(), 0, batch=2,
                           seq_len=256, microbatches=2, steps=2)
    assert "train: mamba2-130m" in capsys.readouterr().out
