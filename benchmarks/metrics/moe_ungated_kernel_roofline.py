"""moe_ungated_kernel_roofline.<items>: ``moe_kernel_roofline``'s rule for
routed experts that are not gated, in %: the least seconds the chip could
take for the grouped products over the device seconds of their
operations in the traced epochs.

An expert ``Wd act(Wu x)`` is two grouped products a row forward and six
trained where a gated one is three and nine, so ``moe_kernel_roofline``
(which counts three through ``counts_routed``) would set this layer's
roof half too high.  Everything else is that reader's: the seconds are
those of ``grouped_matmul``, ``grouped_matmul_t``, ``grouped_matmul_dw``
(or XLA's ``ragged-dot``); rows and experts with a row come from the
program's counters through ``measured.routed_rows``, as the window's
mean an epoch times the traced epochs, shared evenly among the routed
layers; the roof (``counts_hybrid.grouped_products_roof_seconds``) is the
larger of work over the bf16 peak and bytes over the HBM bandwidth, with
the products a row that ``measured.routed_layers`` states.

No counter, no trace, no such operation, or a layer that does not state
two products a row gives nothing.
"""

import counts_hybrid
from metrics.moe_kernel_roofline import kernel_seconds


def read(run):
    trace, m = run.get("trace"), run["measured"]
    rows, layers = m.get("routed_rows"), m.get("routed_layers")
    if not trace or not trace.get("epochs_in_window") or not rows \
            or not layers or not m.get("epochs") \
            or any(l.get("products_forward") != 2 for l in layers):
        return None
    seconds = kernel_seconds(trace["seconds_by_op"])
    if seconds <= 0:
        return None
    traced = trace["epochs_in_window"] / m["epochs"] / len(layers)
    train, valid = rows.get("train", {}), rows.get("validation", {})
    roof = sum(counts_hybrid.grouped_products_roof_seconds(
        layer, train.get("routed", 0) * traced,
        valid.get("routed", 0) * traced,
        train.get("experts_active", 0) * traced,
        valid.get("experts_active", 0) * traced,
        m["batches_per_epoch"]["train"] * trace["epochs_in_window"],
        run["peaks"], run["chips"])[0] for layer in layers)
    return 100.0 * roof / seconds
