"""Whole step: model FLOP per step (the configuration file's count, never
recounted) times the steps in the traced steady span, over the span's
seconds times the chip's bf16 peak. Profiler trace."""

UNIT = "%"


def read(record):
    lead = (record["trace"] or {}).get("lead")
    if not lead or not lead.get("steps") or not record["peaks"]:
        return None
    rate = record["flop_per_step"] * lead["steps"] / lead["span_s"]
    return 100.0 * rate / record["peaks"]["bf16_flops_per_s"]
