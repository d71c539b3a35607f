"""95th percentile, over the requests due inside the window, of first
token time minus DUE time (a request that failed or did not finish counts
from its due time to the end of the load).  What a chat user feels first,
and an end-to-end metric by nature: it is kept here, without a bound, while
a window holds some tens of requests, because one engine step is a quarter
of its value and it then swings by more than any bound could allow
(PERF.md, sections 2 and 6)."""
from harness.stats import percentile


def read(observed):
    return percentile(observed.get("ttft_s") or [], 0.95)
