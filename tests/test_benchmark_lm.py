"""The language-model cell's fast cases, run with the repository's tests:
`benchmarks/test_lm_cell.py` (the `rehearsal_lm_tiny` fixture end to end,
traced and untraced, and the `lm_reduce` readers). They live beside the
code under `benchmarks/`; the driver runs `pytest tests/`."""

from benchmarks.test_lm_cell import *  # noqa: F401,F403
