#!/usr/bin/env python
"""Env-hatch documentation lint — thin shim over graftlint's
hatch-registry pass (xllm_service_tpu/analysis/hatch_registry.py; run in
tests via tests/test_mixed_step.py). ISSUE 10 widened the PR-9
`XLLM_*_KERNEL` check to EVERY `XLLM_*` env hatch read by the package
or the bench entry points: each must have a row (with a stated default)
in docs/ARCHITECTURE.md's hatch tables, and every row must still match
a live hatch. `python scripts/graftlint.py --pass hatch-registry` is
equivalent.

Exit status 0 = clean; 1 = violations (listed on stderr).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    from xllm_service_tpu.analysis import (
        HatchRegistryPass, Project, run_passes,
    )

    res = run_passes(
        [HatchRegistryPass()], Project.load(REPO), check_stale_waivers=False
    )
    for f in res.findings:
        print(f"kernel-hatch lint: {f.render()}", file=sys.stderr)
    if not res.findings:
        print("kernel-hatch lint: OK (graftlint hatch-registry pass)")
    return 1 if res.findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
