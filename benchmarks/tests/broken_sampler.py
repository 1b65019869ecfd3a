#!/usr/bin/env python3
"""run.py with the timed path broken underneath: every token the program's
sampler produces is altered where it is produced (id + 1), so what the
engine serves, feeds back and streams is no longer the model's choice.
test_run.py drives a whole rehearsal through this and must see `correct`
come out false. Takes run.py's arguments."""
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from xllm_service_tpu.ops import sampling  # noqa: E402

sound = sampling.sample_tokens


def altered(logits, *args, **kwargs):
    tokens, logprob, logprobs = sound(logits, *args, **kwargs)
    return (tokens + 1) % logits.shape[-1], logprob, logprobs


if __name__ == "__main__":
    sampling.sample_tokens = altered
    run_py = os.path.join(ROOT, "benchmarks", "run.py")
    sys.argv[0] = run_py
    runpy.run_path(run_py, run_name="__main__")
