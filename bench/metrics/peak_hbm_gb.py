"""peak_hbm_gb: the device allocator's ``peak_bytes_in_use`` on the fullest
chip after the window, in GB (1e9 bytes)."""


def read(rec):
    return None if rec.peak_bytes is None else rec.peak_bytes / 1e9
