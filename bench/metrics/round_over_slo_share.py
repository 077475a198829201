"""Share of the window's decode rounds whose host-clock time exceeds the
TPOT limit (qos_s) the scheduler was given, in %."""


def read(w):
    r = w.rec["rounds"]
    if not r:
        return None
    qos = w.cell.conf["scheduler"]["qos_s"]
    return 100.0 * sum(1 for x in r if x.t1 - x.t0 > qos) / len(r)
