"""95th percentile of the gaps tpot_mean_ms averages, over all of them."""

from harness.readers import percentile


def read(w):
    g = w.rec["gaps"]
    w.info(f"itl_p95_ms over {len(g)} token gaps")
    v = percentile(g, 95)
    return None if v is None else 1e3 * v
