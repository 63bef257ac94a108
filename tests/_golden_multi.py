"""Golden-record generator: the 8-device MESH side of the bit-identity
pin (run as a subprocess — the device count must be fixed before jax
imports).

    python tests/_golden_multi.py           # print records (slow test)
    python tests/_golden_multi.py --write   # (re)write tests/golden/

The committed ``tests/golden/*.json`` files are this script's output;
``tests/test_simshard_golden.py`` asserts the simshard backend
reproduces every byte of them in-process, and the slow lane re-runs
this script to revalidate the mesh side.
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core.listrank import rank_list_with_stats  # noqa: E402

import _simshard_cases as cases_lib  # noqa: E402


def main():
    write = "--write" in sys.argv[1:]
    mesh = make_mesh(cases_lib.SHAPE, cases_lib.AXES)
    if write:
        cases_lib.GOLDEN_DIR.mkdir(exist_ok=True)
    for name, succ, rank, cfg in cases_lib.golden_cases():
        s, r, stats = rank_list_with_stats(succ, rank, mesh, cfg=cfg)
        rec = cases_lib.case_record(s, r, stats)
        print("GOLDEN " + json.dumps({"name": name, **rec}, sort_keys=True))
        if write:
            (cases_lib.GOLDEN_DIR / f"{name}.json").write_text(
                json.dumps(rec, sort_keys=True, indent=1) + "\n")
    print("done")


if __name__ == "__main__":
    main()
