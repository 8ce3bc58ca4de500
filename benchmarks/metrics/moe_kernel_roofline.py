"""moe_kernel_roofline.<items>: the routed experts' grouped products'
share of their roofline, in %: the least seconds the chip could take for
them over the device seconds of their operations in the traced epochs.

Time: the seconds of ``trace.seconds_by_op`` whose operation (the part of
the raw name before `` = ``) names a grouped product: the program's
Pallas kernels ``grouped_matmul``, ``grouped_matmul_t``,
``grouped_matmul_dw``, or XLA's ``ragged-dot`` where that runs instead.

Roof (``counts_routed.grouped_products_roof_seconds``): the larger of
work over the bf16 peak and bytes over the HBM bandwidth.  Work: a routed
row's forward is its three products; a training row counts three times
that, a validation row once.  Rows are the program's counter
``vt_moe_rows_total{kind="routed"}`` by class over the window, as the
window's mean an epoch times the traced epochs, shared evenly among the
routed layers.  Bytes: a product reads the matrix of each expert that
has a row (``vt_moe_active_experts_total``: an expert without rows is
not fetched), the matrices' gradient is written for every held expert,
and every product moves its rows in and out.  At 256 rows an expert the
two bounds are within a fifth of each other.

No counter (``measured.routed_rows``), no trace or no such operation
gives nothing.
"""

import counts_routed

KERNELS = ("grouped_matmul", "ragged-dot", "ragged_dot")


def kernel_seconds(seconds_by_op):
    return sum(secs for name, secs in seconds_by_op.items()
               if any(k in name.split(" = ", 1)[0] for k in KERNELS))


def read(run):
    trace, m = run.get("trace"), run["measured"]
    rows, layers = m.get("routed_rows"), m.get("routed_layers")
    if not trace or not trace.get("epochs_in_window") or not rows \
            or not layers or not m.get("epochs"):
        return None
    seconds = kernel_seconds(trace["seconds_by_op"])
    if seconds <= 0:
        return None
    traced = trace["epochs_in_window"] / m["epochs"] / len(layers)
    train, valid = rows.get("train", {}), rows.get("validation", {})
    roof = sum(counts_routed.grouped_products_roof_seconds(
        layer, train.get("routed", 0) * traced,
        valid.get("routed", 0) * traced,
        train.get("experts_active", 0) * traced,
        valid.get("experts_active", 0) * traced,
        m["batches_per_epoch"]["train"] * trace["epochs_in_window"],
        run["peaks"], run["chips"])[0] for layer in layers)
    return 100.0 * roof / seconds
