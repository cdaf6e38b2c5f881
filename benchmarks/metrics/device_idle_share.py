"""Device: 1 - the union of device-operation intervals over the traced
steady span (first to last step of the trace). Profiler trace."""

UNIT = "%"


def read(record):
    lead = (record["trace"] or {}).get("lead")
    if not lead or not lead.get("steps"):
        return None
    return 100.0 * (1.0 - lead["span_busy_s"] / lead["span_s"])
