"""engine_xla_ms_per_iter: device milliseconds per ALS iteration outside
the Pallas kernel launches: the top-t passes, the k x k solves, the error
trace and the glue XLA generates around the kernels."""

from bench.trace import is_kernel


def read(rec):
    red, w = rec.trace, rec.window
    if red is None or not w.get("fits"):
        return None
    xla_s = red.self_s(op for op in red.ops if not is_kernel(op))
    return 1e3 * xla_s / (w["fits"] * w["iters"])
