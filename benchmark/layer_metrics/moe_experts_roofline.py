"""Share of their roofline that the grouped expert products reach in an
``afmoe`` cell's traced steps, in %: the least time the chip could take
for the rows the expert layers computed here (the program's counters:
rows x 3 x hidden x inter x 2 forward, twice that backward, and the held
experts' weight bytes: ``flops_trinity_mini.moe_experts_work``) over the
device time of the ops named ``moe_gmm``, ``moe_gmm_dx`` and
``moe_gmm_dw`` (``_afmoe.grouped_product_seconds``).  The forward products
that remat repeats and the buffer's padding rows are time spent, not work
needed."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe  # noqa: E402


def read(run: dict):
    peaks, traced = run["peaks"], run["window"].get("traced")
    spent = _afmoe.grouped_product_seconds(run)
    rows = _afmoe.rows_here_per_layer_step(run)
    if peaks is None or not traced or spent <= 0.0 or rows is None:
        return None
    arch, flops = run["config"]["arch"], run["flops"]
    work = _afmoe.counts().moe_experts_work(
        rows, arch["hidden_size"], arch["moe_intermediate_size"],
        arch["experts_held"])
    layers = arch["num_layers"] - arch["num_dense_layers"]
    least = layers * (flops.roofline_seconds(work["fwd"], peaks)[0]
                      + flops.roofline_seconds(work["bwd"], peaks)[0])
    return 100.0 * least * traced["steps"] / spent
