"""Operations and bytes of the core of a softmax attention layer that sees the
whole causal prefix (``kind`` ``full_attention``) or a window of it
(``sliding_attention``), the score and value products between the projections:
for a row of ``T`` tokens and the ``H`` query heads of ``head_dim`` HELD here
of that kind on the ``num_key_value_heads`` key-value heads held, ``q k^T``
and ``P v`` over the head's width for every (query, key) pair the layer's
mask admits, a forward and two backward products each. The pairs are the
EXACT band, ``sum_t min(t + 1, window)`` (the causal half where there is no
window), never rounded up to a kernel's blocks: a kernel that computes whole
blocks does more work than is counted here, so its share cannot pass 100 %.
The bytes are the least any form moves: q and the output a query head, k and
v a key-value head (a group's queries read ONE copy), read or written once in
the forward and, with their gradients, twice more in the backward, in
bfloat16; the scores never touch memory in that form. At 8,192 tokens the
operations bind for both kinds (a window of 512 by nine to one).
"""

KINDS = ("full_attention", "sliding_attention")


def layers(cfg, kind):
    """The published indices of the layers of ``kind`` held here."""
    return [i for i in cfg["layers_held"] if cfg["layer_types"][i] == kind]


def pairs(cfg, kind):
    """(query, key) pairs a row's mask admits in a layer of ``kind``."""
    t = cfg["seq_len"]
    if kind == "full_attention":
        return t * (t + 1) // 2
    w = min(cfg["sliding_window"], t)
    return w * (w + 1) // 2 + (t - w) * w


def core_per_round(cfg, rows, kind):
    """``(operations, bytes)`` of ``rows`` rows through every held layer of
    ``kind``, forward and backward."""
    if kind not in KINDS:
        raise ValueError(f"kind {kind!r}: one of {KINDS}")
    t, width, kv = cfg["seq_len"], cfg["head_dim"], cfg["num_key_value_heads"]
    ops = nbytes = 0
    for i in layers(cfg, kind):
        h = cfg["num_attention_heads_per_layer"][i]
        ops += rows * 2 * 3 * h * 2 * width * pairs(cfg, kind)
        nbytes += rows * 3 * t * (2 * h + 2 * kv) * width * 2  # q, out; k, v
    return ops, nbytes


def least_seconds(cfg, rows, kind, peaks):
    """The least time a chip of ``peaks`` could take for that: the larger of
    operations over its bf16 peak and bytes over its HBM peak."""
    ops, nbytes = core_per_round(cfg, rows, kind)
    return max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def roofline_share(ctx, scope, kind):
    """Percent of its roofline that the core of the layers of ``kind`` ran
    at: that least time for the rounds the trace holds over the device time
    under ``scope``; ``None`` where the trace has no such scope."""
    seconds = (ctx["trace"] or {}).get("busy_by_scope", {}).get(scope)
    if not seconds:
        return None
    cell = ctx["cell"]
    least = least_seconds(cell.config, cell.samples_per_round, kind, ctx["peaks"])
    return 100.0 * least * ctx["traced_rounds"] / seconds
