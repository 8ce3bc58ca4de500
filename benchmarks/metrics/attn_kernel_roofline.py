"""attn_kernel_roofline.<items>: the flash attention kernels' share of
their roofline, in %: the attention core's model FLOPs in the traced
epochs over the device seconds of the kernels' own operations, over
chips x the bf16 peak.

Time: the seconds of ``trace.seconds_by_op`` whose operation (the part
of the raw name before `` = ``; what follows names operands) is one of
the kernels the program names: ``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``.

Work, a sequence a layer, as ``counts.walk`` counts an attention layer's
core: forward 2 x T^2 x E (two products, QK^T and PV, of 2 x T^2 x E
each, the causal half of them), backward twice that (four products:
dV, dP, dQ, dK; the QK^T that the backward kernels compute again is no
model work).  A training sequence counts 3 x forward, a validation
sequence 1 x; the traced stretch holds ``epochs_in_window`` x
(n_train, n_valid) sequences.

Roof: at T = 2048, D = 64 the kernels are compute-bound -- a block of
queries reads K and V once per T/block_q, about T/4 = 512 FLOP a byte
against the chip's ridge of 197e12 / 819e9 = 240 -- so the roof is the
bf16 peak, not the memory's.

``run`` carries no cell name.  The cell is the one among those this
metric's own ``per_layer`` entries list whose files reproduce
``measured.items_per_epoch`` and ``measured.train_flops_per_item``;
none or two give nothing.  T, E, the attention layers and ``causal``
come from the cell's files through ``counts.walk``, never from the
program.
"""

import config_io
import counts

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
FAMILY = "attn_kernel_roofline"


def kernel_seconds(seconds_by_op):
    """Seconds of the operations that are one of ``KERNELS``."""
    return sum(secs for name, secs in seconds_by_op.items()
               if any(k in name.split(" = ", 1)[0] for k in KERNELS))


def find_cell(measured):
    """(cell, cfg) of the listed cell that ``measured`` came from."""
    bench = config_io.load_benchmark()
    listed = {w for m in bench["per_layer"]
              if m["name"].split(".")[0] == FAMILY
              for w in m.get("workloads", ())}
    found = []
    for entry in bench["workloads"]:
        if entry["name"] not in listed:
            continue
        cell = config_io.load_cell(entry["name"])
        cfg = config_io.load_config(entry["config"])
        traffic = cell["traffic"]
        c = counts.model_counts(cfg, traffic)
        if int(traffic["n_train"]) * c["items_per_row"] \
                == measured["items_per_epoch"] \
                and c["train_flops_per_item"] \
                == measured["train_flops_per_item"]:
            found.append((cell, cfg))
    return found[0] if len(found) == 1 else None


def core_flops_per_sequence(cfg, traffic):
    """Forward FLOPs of the attention cores of one sequence, all layers:
    what ``counts.walk`` counts for an attention layer less its four
    projections (2 x T x their parameters)."""
    return sum(flops - 2 * shape[0] * params
               for _, kind, params, flops, _, shape
               in counts.walk(cfg, traffic) if kind == "attention")


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("epochs_in_window"):
        return None
    seconds = kernel_seconds(trace["seconds_by_op"])
    if seconds <= 0:
        return None
    found = find_cell(run["measured"])
    if found is None:
        return None
    cell, cfg = found
    traffic = cell["traffic"]
    sequences = 3 * int(traffic["n_train"]) + int(traffic["n_valid"])
    flops = trace["epochs_in_window"] * sequences \
        * core_flops_per_sequence(cfg, traffic)
    peak = run["peaks"]["flops_bf16"] * run["chips"]
    return 100.0 * flops / seconds / peak
