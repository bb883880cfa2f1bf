"""warmup_fit_s: host seconds of the first fit in set-up: tracing, Pallas
lowering, compiling or loading from the cache, and the fit itself."""


def read(rec):
    return rec.setup.get("warmup_fit_s")
