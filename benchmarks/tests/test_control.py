"""The control at a size a test run can hold: the tiny model in float32
on the CPU (XLA:CPU accumulates bfloat16 dots in bfloat16, so bfloat16 there
says nothing about the chip), served soundly and with an int8 KV cache and int8 weights.
The controls must read at least three times the sound runs' number; at
the cell's own size, on the chip, they must come out as not correct
(PERF.md section 2 has those readings)."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def control(mode):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_lowprec.py"), "--config", "rehearse-tiny",
         "--mode", mode, "--seeds", "21", "22", "--rehearse"],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["kv-int8", "w-int8"])
def test_lower_precision_reads_far_from_the_sound_runs(mode):
    sound, low = control("sound"), control(mode)
    assert all(sound["verdicts"]), sound
    assert low["logprob_mse_min"] >= 3 * sound["logprob_mse_max"], (sound, low)
