"""device_idle_share.<items>: 1 - (union of the device's operation
intervals) / (traced whole epochs), in %.  Source: the profiler's trace."""


def read(run):
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
