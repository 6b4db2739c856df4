"""Share of the traced window in which the device was idle while one of
``CNNEngine``'s threads did host work (a span outside ``program_spans.WAIT``),
to the nanosecond (``program_spans.work_idle_s``)."""
import program_spans


def read(rec):
    return program_spans.engine_work_idle_pct(rec)
