"""The paged decode-attention kernel's share of its roofline, in %: the
least time the chip needs for its calls in the traced window (the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth; bytes are the active
slots' live K/V pages plus q and out, by the configuration's ``counts``)
over their device time."""

KERNEL = "paged_decode_attention"
PAGE_TOKENS = 64        # ops.decode_attention's page


def read(w):
    if w.trace is None:
        return None
    t = w.trace.op_time(KERNEL)
    rounds = w.rec["rounds"]
    if t <= 0 or not rounds:
        return None
    counts = w.cell.arch.counts
    m = counts.dims(w.cell.cfg)
    calls = m["L"]
    by = sum(calls * counts.decode_attention_bytes(m, r.contexts,
                                                   w.cell.slots, PAGE_TOKENS)
             for r in rounds)
    fl = sum(calls * counts.decode_attention_flops(m, r.contexts)
             for r in rounds)
    t_mem = by / w.peaks["hbm_bytes_per_s"]
    t_fl = fl / w.peaks["bf16_flops_per_s"]
    w.info(f"{KERNEL}: {t:.6f} s device time, {by:.0f} bytes, {fl:.0f} "
           f"FLOPs; bound by {'memory' if t_mem >= t_fl else 'compute'}")
    return 100.0 * max(t_mem, t_fl) / t
