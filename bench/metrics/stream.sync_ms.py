"""Median host time of ``StreamServer.push``'s ``sync`` span:
``bool(emitted)``, the wait for the frame's step to finish."""
import program_spans


def read(rec):
    return program_spans.median_ms(rec, "sync")
