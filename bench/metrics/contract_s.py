"""contract_s (s, mean per solve): device-synced wall time of local
contraction and restoration: the prep and post stages (sum of the
stage attempts' walls)."""
from harness import stage_wall


def read(run):
    return stage_wall(run, lambda label: label in ("prep", "post"))
