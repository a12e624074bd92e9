"""peak_hbm_gb: the device allocator's ``peak_bytes_in_use`` on the
fullest chip, read after the window, in GB (1e9 bytes)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
