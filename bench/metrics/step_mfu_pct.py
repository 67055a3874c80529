"""The step's share of the chip's bf16 peak: operations that the
window's real rows need (padding rows excluded; the family's
``request_flops``) over the device time of their programs times the
peak."""


def read(run):
    if not run.traced():
        return None
    calls = run.traced_window_calls()
    busy = sum(dev for _, _, dev in calls)
    if busy <= 0.0:
        return None
    work = sum(c.rows for c, _, _ in calls) * run.family.request_flops(
        run.config, run.prompt, run.gen)
    return 100.0 * work / (busy * run.peak["bf16_flops_per_s"])
