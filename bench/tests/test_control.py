"""The control: the plain reference at ``high`` (three bfloat16 passes, one
step below the float32 the configurations state) put in the program's
place must fail the cell's check.

On the chip the control is read at each cell's own size with
``bench/calibrate.py``, where it reads a thousand times what the program
does and fails the cell's limits.  Here the fit cells run at the largest
size a test run holds: Reuters at its full size, PubMed journals with each
side and budget cut to a quarter.  Only the references run, not the
program.  On a TPU the control must fail the cell's limits; on the CPU it
reads only about ten times what sound fits do (there XLA's k x k solves
and its own products stay at float32), which is under the limits that the
chip's parting fits need, so there it must read at least
:data:`CPU_CONTROL_FLOOR`, five times the program's gaps on the fits that
agree."""
import copy

import pytest

from bench import corpus as C
from bench import harness

#: the least the control reads on the CPU, five times the 1e-6 to 3e-6
#: that sound fits read
CPU_CONTROL_FLOOR = 1.5e-5

#: the PubMed-journals configuration cut to a quarter a side
QUARTER = {"n_terms": 5028, "n_docs": 1878}


def _state(cell, seed):
    cfg = cell.config
    c = cfg["corpus"]
    corpus = C.journal_corpus(seed, c["n_terms"], c["n_docs"],
                              c["n_journals"], c["terms_per_doc"],
                              c["topic_strength"], c["zipf_exponent"])
    driver = harness.driver_of(cell)
    return driver, driver.State(cfg, cell.traffic, seed, corpus)


def _cut(cell):
    cell = copy.deepcopy(cell)
    if cell.config["corpus"]["n_terms"] == 20112:
        cell.config["corpus"].update(QUARTER)
        cell.config["fit"].update(t_u=1250, t_v=500)
    return cell


@pytest.mark.parametrize("workload", ["reuters-21578.fit",
                                      "pubmed-journals.fit"])
def test_the_control_fails_the_check(workload):
    import jax

    cell = _cut(harness.load_cell(workload))
    driver, st = _state(cell, seed=2**31 + 99)
    readings = driver.control(st, "high")
    limits = dict(cell.limits)
    if jax.devices()[0].platform != "tpu":
        limits.update(u_gap_3rd=CPU_CONTROL_FLOOR, v_gap_3rd=CPU_CONTROL_FLOOR)
    failed = [name for name, limit in limits.items()
              if readings[name] > limit]
    assert failed, (readings, cell.limits)
