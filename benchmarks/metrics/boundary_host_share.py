"""boundary_host_share.<items>: the host's work at an epoch boundary as a
share of the epoch, in %: the seconds of the program's ``epoch_decision``
spans (anomaly check, decision, recorder, status, best-state copy,
rollback, the loader's advance) and ``snapshot`` span over the epoch's
cycle, on the window's typical epoch.  The device has nothing to
do meanwhile.  Source: the program's spans (train_run_spans.py)."""

from metrics import train_run_spans


def read(run):
    return train_run_spans.share(run, ("epoch_decision", "snapshot"))
