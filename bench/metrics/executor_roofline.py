"""Share of the roofline reached by the int8 executor: the least time one
call can take (``work.bound_s``: operations over the int8 peak, or the
input, logits and parameters over HBM bandwidth, whichever is longer),
over the device time of one run of the executor's XLA module in the trace."""
import work


def read(rec):
    t, peak = rec.get("trace"), rec.get("peak")
    if not t or not peak or "executor_module" not in rec:
        return None
    mod = t["modules"].get(rec["executor_module"])
    if not mod or mod["n"] == 0 or mod["s"] <= 0:
        return None
    bound, _ = work.bound_s(rec["cfg"], rec["batch"], peak)
    return 100.0 * bound / (mod["s"] / mod["n"])
