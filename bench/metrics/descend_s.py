"""descend_s (s, mean per solve): device-synced wall time of the SRS
descend levels, i.e. the ruler chase (sum of the stage attempts'
walls)."""
from harness import stage_wall


def read(run):
    return stage_wall(run, lambda label: label.startswith("descend@"))
