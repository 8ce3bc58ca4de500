"""step_mfu.<items>: the whole step's share of the chip's bf16 peak, in %:
model FLOPs per item from the configuration's shapes (counts.py) x items
per second / (chips x peak).  The rate is taken over the traced whole
epochs (all their items over all their seconds, validation and epoch
boundary included), so that it is read on the same stretch as the device's
busy time and the kernels' times and does not hold the profiler's own
start and stop; where the trace marks no epochs, over the run's window."""


def read(run):
    peak = run["peaks"]["flops_bf16"] * run["chips"]
    m, trace = run["measured"], run.get("trace")
    rate = m["items_per_s"]
    if trace and trace.get("epochs_in_window"):
        rate = trace["epochs_in_window"] * m["items_per_epoch"] \
            / trace["window_s"]
    return 100.0 * m["train_flops_per_item"] * rate / peak
