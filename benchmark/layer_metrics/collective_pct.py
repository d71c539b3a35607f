"""Share of the traced window in which a collective was in flight
(all-reduce, reduce-scatter, all-gather, collective-permute, all-to-all;
an asynchronous one from its start op to its done op), mean over chips."""


def read(observed):
    trace = observed.get("trace")
    return trace and trace["collective_pct"]
