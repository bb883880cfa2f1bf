"""stream_roofline: the streamed fit's sparse-product kernels' share of
their roofline, in %.

The work (:mod:`bench.work_stream`, from each chunk's nnz and shapes) is
what the product launches of the traced window's fits computed: in every
chunk the online passes' half-steps, the fold-in's product and the seed
statistics' product.  The time is the self time of the Pallas kernels whose
HLO name matches :data:`PRODUCT_KERNEL` in the device trace (the fused
``bsr_spmm_gram`` of the passes and the ``bsr_spmm`` of the fold-in and the
seed statistics).  Where none matches, the reading is left out and the
operations seen are printed."""

import re
import sys

from bench import work_stream
from bench.trace import is_kernel, names_seen

PRODUCT_KERNEL = re.compile(r"spmm", re.I)


def read(rec):
    red, w = rec.trace, rec.window
    if red is None or not w.get("fits") or rec.peaks is None \
            or "chunks" not in rec.setup:
        return None
    kernel_s = red.self_s(op for op in red.ops
                          if is_kernel(op) and PRODUCT_KERNEL.search(op.name))
    if kernel_s <= 0:
        print(f"stream_roofline: no product kernel in the trace; ops seen: "
              f"{names_seen(red)}", file=sys.stderr)
        return None
    cfg = rec.cell.config
    per_fit = work_stream.fit_products(cfg["corpus"]["n_terms"],
                                       rec.setup["chunks"], cfg["k"],
                                       rec.setup["passes"])
    return 100.0 * (per_fit * w["fits"]).roofline_s(rec.peaks) / kernel_s
