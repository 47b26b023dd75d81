"""Share of the traced sub-window in which no operation ran on the device
(mean over the cell's devices): 100 x (1 - busy / window)."""


def read(facts: dict):
    trace = facts.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
