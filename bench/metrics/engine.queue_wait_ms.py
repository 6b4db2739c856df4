"""Median over the window's requests of the wait from ``submit`` to the
batch closing, from ``CNNEngine``'s per-batch request records."""
import program_spans


def read(rec):
    return program_spans.queue_wait_ms(rec)
