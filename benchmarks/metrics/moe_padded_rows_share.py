"""moe_padded_rows_share.<items>: the share of the rows that the routed
experts' grouped products ran that no route filled, in %: (computed -
routed) / computed over the window, every class and routed layer
together.  An expert's rows are padded to whole tiles of the kernel's
row block, so this is what the tile costs at the cell's rows an expert.
Source: the program's counter ``vt_moe_rows_total{kind}``
(``measured.routed_rows``); a program without it gives nothing."""


def read(run):
    rows = run["measured"].get("routed_rows")
    if not rows:
        return None
    computed = sum(kinds.get("computed", 0) for kinds in rows.values())
    routed = sum(kinds.get("routed", 0) for kinds in rows.values())
    if computed <= 0:
        return None
    return 100.0 * (computed - routed) / computed
