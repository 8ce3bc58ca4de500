"""data_wait_share.<items>: the seconds the trainer's loop blocked on its
feed (vt_train_phase_seconds{phase="data_wait"}, summed over the window)
over the window's seconds, in %.  Source: the program's counter."""


def read(run):
    m = run["measured"]
    if m.get("data_wait_s") is None:
        return None
    return 100.0 * m["data_wait_s"] / m["window_s"]
