"""peak_hbm_gib (GiB): the device runtime's ``peak_bytes_in_use`` on the
fullest device of the cell, read after the window."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2**30
