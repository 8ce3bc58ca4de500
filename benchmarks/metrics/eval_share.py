"""eval_share.<items>: the validation pass's share of an epoch, in %: the
seconds of the program's ``eval`` span (dispatch of the validation
batches and the drain of their sums) over the epoch's cycle, on the
window's typical epoch.  Validation items do not count in the cell's
rate, so this is what the rate pays for them.  Source: the program's
spans (train_run_spans.py)."""

from metrics import train_run_spans


def read(run):
    return train_run_spans.share(run, ("eval",))
