"""Requests per dispatched batch in the window, from ``CNNEngine``'s
``ServeStats`` counters."""


def read(rec):
    if not rec.get("batches"):
        return None
    return rec["requests"] / rec["batches"]
