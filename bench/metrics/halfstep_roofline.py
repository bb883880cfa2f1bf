"""halfstep_roofline: the sparse-product kernels' share of their roofline,
in %.

The work (:mod:`bench.work`, from nnz and shapes) is what the product
launches of the traced window computed: in every fit, both half-steps'
products and Grams in each iteration, and the one ``A V`` with ``V^T V``
that ``fit`` adds at its end for the streaming statistics.  The time is
the self time of the launches in the device trace: Pallas kernels whose
HLO name matches :data:`PRODUCT_KERNEL`.  Where none matches, the reading
is left out and the operations seen are printed."""

import re
import sys

from bench import work
from bench.trace import is_kernel, names_seen

#: the product kernels' HLO names (``bsr_spmm_gram.N`` for the fused
#: product and Gram)
PRODUCT_KERNEL = re.compile(r"spmm", re.I)


def read(rec):
    red, w = rec.trace, rec.window
    if red is None or not w.get("fits") or rec.peaks is None:
        return None
    kernel_s = red.self_s(op for op in red.ops
                          if is_kernel(op) and PRODUCT_KERNEL.search(op.name))
    if kernel_s <= 0:
        print(f"halfstep_roofline: no product kernel in the trace; ops seen: "
              f"{names_seen(red)}", file=sys.stderr)
        return None
    cfg = rec.cell.config
    n, m = cfg["corpus"]["n_terms"], cfg["corpus"]["n_docs"]
    nnz, k = rec.setup["nnz"], cfg["k"]
    per_fit = (work.iteration_halfsteps(n, m, nnz, k) * w["iters"]
               + work.halfstep(nnz, n, m, k))
    return 100.0 * (per_fit * w["fits"]).roofline_s(rec.peaks) / kernel_s
