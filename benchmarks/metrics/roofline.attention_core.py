"""Kernels: the attention core (scores, softmax, values) against the chip's
roofline: the FLOP and the least bytes of the WORK of one step (forward
and both gradients over the keys each query may see, from the
configuration: `benchmarks/count_lm_flops.py::attention_core_work`) over
the device time under scope `lm.attention_core` (recomputation included in
the time and not in the work), each against its peak of `peaks.json`; the
larger share. Profiler trace + configuration."""

from benchmarks import count_lm_flops, lm_reduce

UNIT = "%"


def read(record):
    noted = lm_reduce.noted()
    if noted is None:
        return None
    sizes, traffic = noted
    return lm_reduce.roofline(
        record, "lm.attention_core",
        count_lm_flops.attention_core_work(
            sizes, traffic["batch"], traffic["seq"]
        ),
    )
