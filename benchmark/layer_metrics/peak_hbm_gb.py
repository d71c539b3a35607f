"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, in GB."""


def read(observed):
    peak = observed.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
