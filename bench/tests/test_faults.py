"""A run at a tiny size on the CPU, the chip check skipped: sound, it is
correct; with the timed path broken underneath, ``correct`` comes out
false, once for each fault a cell can have (one chip, so no exchange
between chips to leave out), and for a fault that spares one fit of the
window and one that breaks only the budget."""
import time

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse

from bench import harness


def _run(cell, seed=2**31 + 5):
    return harness.execute(cell, seed, 0.5, False, time.perf_counter())


def _state_unchanged(monkeypatch):
    """Every fit hands back its initial U as the fitted factor."""
    import repro.nmf.solvers as solvers

    real = solvers.als_nmf

    def fake(a, u0, **kw):
        return real(a, u0, **kw)._replace(u=u0)

    monkeypatch.setattr(solvers, "als_nmf", fake)


def _fit_answer_altered(monkeypatch):
    """The fitted U comes out 1% too large."""
    import repro.nmf.solvers as solvers

    real = solvers.als_nmf

    def fake(a, u0, **kw):
        res = real(a, u0, **kw)
        return res._replace(u=res.u * 1.01)

    monkeypatch.setattr(solvers, "als_nmf", fake)


def _fit_half_left_out(monkeypatch):
    """The ingest leaves out the second half of the documents."""
    from repro.backend.pallas_bsr import PallasBsrBackend

    real = PallasBsrBackend.prepare

    def fake(self, a, dtype=None, bcap=None):
        if hasattr(a, "tocsc"):
            keep = np.arange(a.shape[1]) < a.shape[1] // 2
            a = (a @ scipy.sparse.diags(keep.astype(a.dtype))).tocsr()
            a.eliminate_zeros()
        return real(self, a, dtype=dtype, bcap=bcap)

    monkeypatch.setattr(PallasBsrBackend, "prepare", fake)


def _most_fits_altered(monkeypatch):
    """The fitted U comes out 1% too large on every fit but the second of
    the window (the set-up fit is the first call): a window of one fit
    has none spared."""
    import repro.nmf.solvers as solvers

    real, calls = solvers.als_nmf, []

    def fake(a, u0, **kw):
        calls.append(None)
        res = real(a, u0, **kw)
        return res if len(calls) == 3 else res._replace(u=res.u * 1.01)

    monkeypatch.setattr(solvers, "als_nmf", fake)


def _budget_exceeded(monkeypatch):
    """The fitted U holds one non-zero above its budget, too small to move
    the factors' gap."""
    import repro.nmf.solvers as solvers

    real = solvers.als_nmf

    def fake(a, u0, **kw):
        res = real(a, u0, **kw)
        zero = jnp.argmin(jnp.where(res.u.ravel() == 0, 0, 1))
        extra = 1e-9 * jnp.max(res.u)
        return res._replace(u=res.u.ravel().at[zero].set(extra)
                            .reshape(res.u.shape))

    monkeypatch.setattr(solvers, "als_nmf", fake)


FIT_FAULTS = [_state_unchanged, _fit_answer_altered, _fit_half_left_out,
              _most_fits_altered, _budget_exceeded]


@pytest.mark.parametrize("kept,t,over", [
    ([5, 4, 3, 0, 0], 3, 0),       # the budget exactly
    ([5, 4, 3, 3, 3, 0], 3, 0),    # two more, tied with the t-th
    ([5, 4, 3, 3, 2, 0], 3, 1),    # one below the t-th kept
    ([5, 4, 3, 2, 1], 3, 2),
    ([5, 4, 0, 0, 0], 3, 0),       # under the budget
    ([5, 4, 3, 2, 1], None, 0),    # no budget
])
def test_over_budget_counts_all_but_ties(kept, t, over):
    from bench.harness import driver_of, load_cell

    driver = driver_of(load_cell("reuters-21578.fit"))
    assert driver._over_budget(np.array(kept, np.float32), t) == over


@pytest.mark.parametrize("workload", ["pubmed-journals.fit",
                                      "reuters-21578.fit"])
def test_a_sound_run_is_correct(tiny_cell, workload):
    result = _run(tiny_cell(workload))
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault", FIT_FAULTS, ids=lambda f: f.__name__)
def test_a_broken_fit_is_not_correct(tiny_cell, monkeypatch, fault):
    fault(monkeypatch)
    result = _run(tiny_cell("pubmed-journals.fit"))
    assert not result["correct"], result["compared"]

