"""setup_s: seconds from the process's start to the start of the measured
window: JAX start, corpus, ingest, the first fit and every warm-up."""


def read(rec):
    return rec.setup_s
