"""The arithmetic of `correct`: gaps between what the program held and what
the plain reference holds, and their limits.

A gap of norms is |‖program‖ - ‖reference‖| over the larger of the
reference's norm of that leaf and of its median leaf: taken by the worst
leaf and by the median leaf. A gap of batch statistics is, for one batch
norm, ‖program's variances - reference's‖ over ‖reference's‖, and
‖program's means - reference's‖ over ‖reference's standard deviations‖:
likewise by the worst and the median batch norm. Which of them a cell
compares, and under what limit, is in its workload file; what is read and
how is in the check the cell names (`checks/`); PERF.md has the readings
each limit was set from.
"""

from __future__ import annotations

import sys

import numpy as np


def norm_gaps(program, reference, keep=None):
    """(worst leaf's gap, median leaf's gap) of two {leaf: norm} dicts."""
    median = float(np.median(list(reference.values())))
    gaps = {}
    for key, ref in reference.items():
        if keep is None or key in keep:
            gap = abs(program[key] - ref) / max(ref, median, 1e-30)
            gaps[key] = gap if np.isfinite(gap) else float("inf")
    where = max(gaps, key=gaps.get)
    print(
        "worst leaf %s: held %.6g reference %.6g median leaf %.6g"
        % (where, program[where], reference[where], median),
        file=sys.stderr,
    )
    return gaps[where], float(np.median(list(gaps.values())))


def stat_gaps(program, reference):
    """{name: gap} of two {batch norm: (mean, variance)} dicts: worst and
    median gap of the variances, worst and median gap of the means."""
    by_var, by_mean = {}, {}
    for name, (ref_mean, ref_var) in reference.items():
        mean, var = (np.asarray(v, np.float64) for v in program[name])
        ref_mean = np.asarray(ref_mean, np.float64)
        ref_var = np.asarray(ref_var, np.float64)
        by_var[name] = np.linalg.norm(var - ref_var) / np.linalg.norm(ref_var)
        by_mean[name] = np.linalg.norm(mean - ref_mean) / np.linalg.norm(
            np.sqrt(ref_var)
        )
    read = {}
    for key, gaps in (("stats_var", by_var), ("stats_mean", by_mean)):
        where = max(gaps, key=gaps.get)
        print("worst batch norm by %s: %s %.6g" % (key, where, gaps[where]),
              file=sys.stderr)
        read[key] = float(gaps[where])
        read[key + "_median"] = float(np.median(list(gaps.values())))
    return read


def limited(read, limits, exact=()):
    """{name: [value, limit]} of the numbers the cell's file gives a limit
    (each must have been read) and of those held to 0; a number read with
    no limit is printed, and not compared."""
    missing = sorted(set(limits) - set(read))
    if missing:
        raise SystemExit(
            "benchmarks: the cell limits %s, which its check does not read"
            % missing
        )
    numbers = {}
    for name, value in read.items():
        if name in limits:
            numbers[name] = [value, limits[name]]
        elif name in exact:
            numbers[name] = [value, 0.0]
        else:
            print("read %s %.6g, not compared" % (name, value),
                  file=sys.stderr)
    return numbers


def passed(numbers):
    return all(
        np.isfinite(value) and value <= limit
        for value, limit in numbers.values()
    )
