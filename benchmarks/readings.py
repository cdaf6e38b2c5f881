"""The readings a cell's limits are set from, taken in one process.

    python -m benchmarks.readings <cell> <first seed> <seeds> <control seeds> [steps] [arms]

For each seed: the program's first steps through `Estimator.train` (as in
a run, without the window) against the float32 reference: the lower
readings. For the first `<control seeds>` of them also, with the reference
put in the program's place: the control (scaled float8 operands), the
second witness (bfloat16 as the configuration states) and the planted
fault of half of each batch left out: the upper readings. `steps` under
the check's own follows fewer (the first gradient and the batch statistics
need one). One JSON line a seed; PERF.md gives the limits set from them.
"""

from __future__ import annotations

import json
import sys


# arm: (arithmetic, share of each batch's rows) of the reference that is
# put in the program's place.
ARMS = {
    "control_fp8": ("fp8", 1.0),
    "witness_bf16": ("bf16", 1.0),
    "fault_half_batch": ("f32", 0.5),
}


def main(argv):
    from benchmarks import run

    name, first, seeds, controls = argv[1], *map(int, argv[2:5])
    cell = run.Cell(name)
    steps = int(argv[5]) if len(argv) > 5 else cell.check.STEPS
    arms = argv[6].split(",") if len(argv) > 6 else list(ARMS)
    if run.start_jax(cell) is None:
        return 3
    for index in range(seeds):
        seed = first + index
        search = run.Search(cell, seed)
        try:
            cell.check.prepare(search, steps)
            search.free()
            # Every number read, compared or not, beside the seed.
            line = {"seed": seed, "program": cell.check.read(search)}
            for arm in arms if index < controls else ():
                line[arm] = cell.check.read(search, *ARMS[arm])
        finally:
            search.close()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
