"""Readings for the limits of ``correct`` (``PERF.md`` section 2): for each
seed, the five compared numbers of the program as the configuration states
it, of the configuration's lower-precision control, and of planted faults.

    python3 benchmark/readings.py --workload <cell> --seeds 11,12,13 --faults 3

One process reads every seed (set-up is most of a run). The benchmark's own
runs never call this; the limits in the configurations' files were set from
its output on the chip. Faults, read on the first ``--faults`` seeds:
``half_batch`` (the reference on the first half of the samples, in the
program's place), ``state_unchanged`` (the first model returned after every
call) and, across chips, ``no_exchange`` (the program with its ring rotation
held still).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness, traffic  # noqa: E402


def program_record(cell, data, overrides=None) -> dict:
    driver = cell.part("driver").Driver(
        cell.config, cell.traffic, data, cell.chips, overrides)
    driver.prepare()
    record = harness.first_calls(driver, harness.Spans())
    driver.free()
    del driver
    gc.collect()
    return record


def control_record(cell, data) -> dict:
    control = cell.config["control"]
    if control["kind"] == "program":
        return program_record(cell, data, control["overrides"])
    import jax.numpy as jnp

    return harness.follow_reference(
        cell, data, products=getattr(jnp, control["products"]))[1]


@contextlib.contextmanager
def rotation_held_still():
    """The exchange between chips left out: every ring rotation of the
    program keeps its blocks where they are."""
    from harp_tpu.collectives import rotation

    init = rotation.Rotator.__init__

    def held(self, *args, **kwargs):
        kwargs["shift"] = 0
        init(self, *args, **kwargs)

    rotation.Rotator.__init__ = held
    try:
        yield
    finally:
        rotation.Rotator.__init__ = init


def unchanged_record(first: dict, like: dict) -> dict:
    still = dict(first)
    return {"quality": like["quality"], "after_1": still, "after_3": still}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse without a chip (no reading counts)")
    args = ap.parse_args(argv)
    cell, device, _ = harness.open_cell(
        args.workload, require_accelerator=not args.cpu)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        data = harness.make_data(cell, seed)
        program = program_record(cell, data)
        control = control_record(cell, data)
        first, reference = harness.follow_reference(cell, data)
        line = {"workload": cell.name, "seed": seed, **device,
                "program": compare.numbers(first, program, reference),
                "control": compare.numbers(first, control, reference)}
        if i < args.faults:
            faults = {"state_unchanged": compare.numbers(
                first, unchanged_record(first, program), reference)}
            _, half = harness.follow_reference(cell, traffic.halved(data))
            faults["half_batch"] = compare.numbers(first, half, reference)
            if cell.chips > 1:
                with rotation_held_still():
                    held = program_record(cell, data)
                faults["no_exchange"] = compare.numbers(first, held, reference)
            line["faults"] = faults
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
