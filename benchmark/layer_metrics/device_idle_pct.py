"""1 - (union of the intervals in which an operation ran on the device) /
(traced window), mean over the chips of the cell."""


def read(observed):
    trace = observed.get("trace")
    return trace and trace["idle_pct"]
