"""ingest_s: host seconds to turn the corpus into the backend's operand on
the device (``get_backend(...).prepare``), in set-up."""


def read(rec):
    return rec.setup.get("ingest_s")
