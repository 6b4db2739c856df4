"""Share of the traced window in which no operation ran on the device."""
import trace_reduce


def read(rec):
    return trace_reduce.idle_pct(rec.get("trace"))
