"""p95 over every request due in the window, from its due time to its result
on the host: the serve tail, which the host's stalls of a second or more
move far in some runs, so it is read per layer beside the end-to-end p90."""


def read(rec):
    return rec.get("request_p95_ms")
