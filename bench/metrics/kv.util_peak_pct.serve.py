"""Peak share of the KV page pool's cells holding live tokens over the
window's ticks, from the pool's own accounting
(``BlockPool.utilization()["pool_util"]``)."""


def read(run):
    end = run["window"][1]
    util = [t.kv_util for t in run["ticks"] if t.start < end]
    return 100.0 * max(util) if util else None
