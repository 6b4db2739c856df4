"""Median per batch of ``CNNEngine``'s host work: its ``stage``, ``h2d``,
``launch`` and ``complete`` spans."""
import program_spans


def read(rec):
    return program_spans.host_ms_per_batch(rec)
