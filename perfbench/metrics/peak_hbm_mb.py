"""peak_hbm_mb: peak device memory of the run, MB (layer: device).

`peak_bytes_in_use` of the fullest device after the traced call, from the
device's allocator counters.  Moves `sim_years_per_s` (a study's width is
bounded by what fits)."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e6
