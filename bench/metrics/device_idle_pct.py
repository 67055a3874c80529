"""Share of the window in which no operation ran on the device,
averaged over the cell's chips."""

from bench import trace as tr


def read(run):
    if not run.traced():
        return None
    w0, w1 = run.window_ns()
    idle = [1.0 - tr.busy_ns(tr.merge(run.trace.ops[d]), w0, w1) / (w1 - w0)
            for d in run.devices()]
    return 100.0 * sum(idle) / len(idle) if idle else None
