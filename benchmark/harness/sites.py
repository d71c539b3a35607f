"""A kernel that one compiled program calls from SEVERAL places (a model's
runs of layers of one kind are a traced loop each) has one operation a call
site in the device trace, all under the kernel's name, and the reduction
(``trace_reduce``: the ten largest operations) may keep some and not the
others.  Nothing in a label says which site it is, so the sites are told
apart by their seconds: the one that runs more layers takes longer."""


def kept_share(trace, kernel, layers_by_site):
    """The share of a kernel's layers whose call sites the reduction kept:
    ``layers_by_site``: how many layers each call site runs.  The largest
    sites are taken as the ones kept.  0.0 where none was, or no trace."""
    if not trace:
        return 0.0
    kept = sum(label.startswith((kernel + ".", kernel + " "))
               for label, _ in trace.get("device_ops") or [])
    layers = sorted(layers_by_site, reverse=True)
    return sum(layers[:kept]) / sum(layers) if layers else 0.0
