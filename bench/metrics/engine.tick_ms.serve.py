"""Mean time of one engine tick in the window, on the benchmark's clock
around ``ServeEngine.tick()``."""


def read(run):
    end = run["window"][1]
    ticks = [t.end - t.start for t in run["ticks"] if t.start < end]
    return 1e3 * sum(ticks) / len(ticks) if ticks else None
