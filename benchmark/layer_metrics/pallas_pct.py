"""Share of the device's busy time spent inside Mosaic (Pallas) custom
calls, from the device trace."""


def read(observed):
    trace = observed.get("trace")
    return trace and trace["group_pct_of_busy"]["pallas"]
