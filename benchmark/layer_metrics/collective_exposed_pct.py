"""The part of ``collective_pct`` during which no other operation ran on
that chip: what a change to the gradient wire can gain at most."""


def read(observed):
    trace = observed.get("trace")
    return trace and trace["collective_exposed_pct"]
