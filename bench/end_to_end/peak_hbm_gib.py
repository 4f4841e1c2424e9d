"""The largest ``peak_bytes_in_use`` over the devices at the window's
close, in GiB: one process per run, so the peak is this cell's own."""


def read(window):
    if not window.memory_peak_bytes:
        return None
    return window.memory_peak_bytes / 2 ** 30
