"""mfu.fit: the whole fit's share of the chip's peak FLOP/s, in %, from the
device trace: the operations every iteration needs
(:func:`bench.work.iteration_flops`, from nnz and shapes) times the
iterations the traced window completed, over the device time those
iterations span (first operation's start to last operation's end, idle
gaps included) and the peak.  It bounds every kernel's share: a kernel
taken off the path leaves its roofline silent but not this."""

from bench import work


def read(rec):
    red, w = rec.trace, rec.window
    if red is None or not red.ops or not w.get("fits") or rec.peaks is None:
        return None
    span_s = (min(max(op.end for op in red.ops), red.window[1])
              - max(min(op.start for op in red.ops), red.window[0]))
    if span_s <= 0:
        return None
    cfg = rec.cell.config
    n, m = cfg["corpus"]["n_terms"], cfg["corpus"]["n_docs"]
    flops = (work.iteration_flops(n, m, rec.setup["nnz"], cfg["k"])
             * w["fits"] * w["iters"])
    return 100.0 * flops / (span_s * rec.peaks["flops_per_s"])
