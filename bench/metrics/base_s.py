"""base_s (s, mean per solve): device-synced wall time of the doubling
base case (sum of the stage attempts' walls)."""
from harness import stage_wall


def read(run):
    return stage_wall(run, lambda label: label.startswith("base@"))
