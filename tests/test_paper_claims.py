"""The paper's claims, checked by the reduced-scale figure runners in
``benchmarks/``: enforced sparsity converges, keeps or improves accuracy,
saves at least 10x memory at tight budgets, spreads nonzeros evenly under
column-wise enforcement, and every top-t select agrees.

Each runner returns ``(rows, derived)``; every boolean in ``derived`` is a
claim.  ``fig9_timing`` is left out: its one claim is a CPU wall-clock
comparison."""
import importlib
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = str(Path(__file__).resolve().parents[1])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RUNNERS = ["fig2_convergence", "fig3_sweep", "fig45_accuracy", "fig6_memory",
           "fig7_distribution", "ablation_topk"]


@pytest.mark.parametrize("runner", RUNNERS)
def test_paper_claims_hold(runner):
    module = importlib.import_module(f"benchmarks.{runner}")
    _, derived = module.run(small=True)
    claims = {name: bool(value) for name, value in derived.items()
              if isinstance(value, (bool, np.bool_))}
    assert claims, f"{runner} reports no claim"
    failed = [name for name, held in claims.items() if not held]
    assert not failed, f"{runner}: {failed} do not hold ({derived})"
