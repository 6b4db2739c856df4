"""Median host time of one ``StreamServer.push`` in the window: the stream
step's dispatch and its emission sync, timed by the harness around each call."""


def read(rec):
    return rec.get("push_p50_ms")
