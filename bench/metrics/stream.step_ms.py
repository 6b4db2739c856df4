"""Median host time of ``StreamServer.push``'s ``step`` span: the launch of
the AOT per-frame step."""
import program_spans


def read(rec):
    return program_spans.median_ms(rec, "step")
