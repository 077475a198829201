"""Time to first token, 90th percentile over the requests due in a
code_long window, censored at the close (``readers.ttft_p90_ms``)."""

from harness.readers import ttft_p90_ms


def read(w):
    return ttft_p90_ms(w)
