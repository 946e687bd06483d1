"""The rotq codec's rotations against their roofline: the least time the chip
could take for a round's rotations (``flops/rotq.py``: the larger of
operations over the bf16 peak and bytes over the HBM peak; the bytes bind,
a row is read once and written once) over the device time under
``fed.codec.rotate``, both for the rounds the trace holds. The pattern of
every kernel's roofline share: operations and bytes from shapes by a function
under ``flops/``, time from the kernel's own scope."""

import math


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    seconds = (t or {}).get("busy_by_scope", {}).get("fed.codec.rotate")
    if not seconds:
        return None
    n_params = sum(math.prod(shape) for _, shape, _ in
                   cell.reference.spec(cell.config)[0])
    flops, nbytes = cell.code("flops", "rotq").rotations_per_round(
        cell.traffic["clients"], n_params)
    least = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_rounds"] / seconds
