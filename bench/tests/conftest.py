"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``
from the root of the checkout.  Pallas kernels run in interpret mode."""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

#: the test size of every configuration: small enough for interpret mode
TINY_CORPUS = {"n_terms": 640, "n_docs": 384}
TINY_ITERS = 4


def tiny(cell):
    """``cell`` cut to :data:`TINY_CORPUS`, with budgets that still bind."""
    from bench import harness

    cfg = copy.deepcopy(cell.config)
    cfg["corpus"].update(TINY_CORPUS)
    cfg["iters"] = TINY_ITERS
    fit = cfg["fit"]
    if fit["t_u"] is not None:
        fit["t_u"] = min(fit["t_u"], 600)
    if fit["t_v"] is not None:
        fit["t_v"] = 250
    return harness.Cell(**{**cell.__dict__, "config": cfg})


@pytest.fixture()
def tiny_cell():
    from bench import harness

    return lambda name: tiny(harness.load_cell(name))
