"""The held experts' grouped product as kernels
(``fedtpu/ops/expert_kernels.py``) against a product written block by block,
on the CPU through the Pallas interpreter: the output of an expert layer's
three products and the gradients of the rows and of all three weight stacks at
the five language cells' block, widths and held experts (a few blocks of
rows; Nemotron-H's 1,856 is no whole number of lanes and is the output's width
in two of the layer's products and the contraction in the third); a chunk
whose pairs all fall on one expert; an expert no pair fell on
(its weights' gradient is exactly zero, and finite); a chunk with no live
block; a last block with one live row. Which body a product takes, and that
the counter says so.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.models import lm_layers
from fedtpu.obs.registry import get_global_registry
from fedtpu.ops import expert_kernels as ek

# (block, in, out, held) of laguna_s_2_1.fl4_seq8k, lfm2_24b_a2b.fl4_b8_seq4k,
# qwen3_next_80b_a3b.fl4_seq8k, joyai_llm_flash.fl4_seq4k and
# nemotron_3_nano_30b_a3b.fl4_seq8k.
CELLS = {"laguna": (128, 3072, 1024, 8), "lfm2": (1024, 2048, 1536, 8),
         "qwen3_next": (128, 2048, 512, 16), "joyai": (256, 2048, 768, 8),
         "nemotron": (128, 2688, 1856, 8)}
# Largest difference over the block-by-block product's largest magnitude.
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}
STACKS = ("rows", "w_gate", "w_up", "w_down")


def _operands(dtype, block, d, width, held, sizes, n_blocks, seed=0):
    """``(rows, w_gate, w_up, w_down)``, a cotangent, the block-to-expert map
    and the count of live blocks for experts that got ``sizes`` pairs each,
    laid out as ``routed_experts`` lays them out: an expert's pairs from a
    block boundary on, the used blocks first, zeros behind a last pair."""
    sizes = np.asarray(sizes)
    blocks = -(-sizes // block)
    live = int(blocks.sum())
    assert live <= n_blocks and len(sizes) == held
    expert = np.minimum(np.searchsorted(
        np.cumsum(blocks), np.arange(n_blocks), side="right"), held - 1)
    is_row = np.zeros(n_blocks * block, bool)
    for e, first in enumerate(np.cumsum(blocks) - blocks):
        is_row[first * block:first * block + sizes[e]] = True
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    is_row = jnp.asarray(is_row)[:, None]
    rows = jnp.where(is_row, normal(keys[0], n_blocks * block, d), 0)
    ws = [normal(keys[1], held, d, width) / d ** 0.5,
          normal(keys[2], held, d, width) / d ** 0.5,
          normal(keys[3], held, width, d) / width ** 0.5]
    ct = jnp.where(is_row, normal(keys[4], n_blocks * block, d), 0)
    return (tuple(a.astype(dtype) for a in [rows] + ws), ct,
            expert.astype(np.int32), live)


def _block_by_block(x, w, expert, live, block, out_dtype=None):
    """A live block times its expert's matrix, zeros for the others."""
    out_dtype = out_dtype or x.dtype
    parts = [
        jnp.dot(x[b * block:(b + 1) * block], w[expert[b]],
                preferred_element_type=jnp.float32).astype(out_dtype)
        if b < live else jnp.zeros((block, w.shape[2]), out_dtype)
        for b in range(x.shape[0] // block)]
    return jnp.concatenate(parts, axis=0)


def _layer(product):
    """An expert layer's three products, as ``routed_experts`` asks for
    them: the hidden rows in the operands' dtype, the output in float32."""
    def layer(rows, w_gate, w_up, w_down):
        hidden = jax.nn.silu(product(rows, w_gate)) * product(rows, w_up)
        return product(hidden, w_down, out_dtype=jnp.float32)
    return layer


def _both(args, ct, expert, live, block):
    """``(kernels', block by block's)``: each ``{"out": ..., operand: its
    gradient}`` under one cotangent."""
    def run(product):
        out, vjp = jax.vjp(_layer(product), *args)
        return dict(zip(STACKS, vjp(ct)), out=out)

    return (
        run(lambda x, w, out_dtype=None: ek.grouped_product(
            x, w, jnp.asarray(expert), jnp.int32(live), block, out_dtype,
            interpret=True)),
        run(lambda x, w, out_dtype=None: _block_by_block(
            x, w, expert, live, block, out_dtype)))


def _same(kernel, plain, dtype):
    for what in ("out",) + STACKS:
        assert kernel[what].shape == plain[what].shape, what
        assert kernel[what].dtype == plain[what].dtype, what
        got, want = (np.asarray(x[what], np.float32) for x in (kernel, plain))
        assert np.isfinite(got).all(), what
        assert np.abs(got - want).max() <= TOLERANCE[dtype] * np.abs(want).max(), what


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_kernels_are_the_product_block_by_block_at_a_cells_shapes(cell):
    """Four live blocks of five on the held experts: one expert with two
    blocks (the second part full: its weight tile is fetched once and its
    gradient summed over the run), two with one, the others with none."""
    block, d, width, held = CELLS[cell]
    sizes = [0] * held
    sizes[1], sizes[2], sizes[held - 1] = block + block // 2, 3, block
    args, ct, expert, live = _operands(
        jnp.float32, block, d, width, held, sizes, n_blocks=5)
    assert live == 4 and list(expert[:4]) == [1, 1, 2, held - 1]
    kernel, plain = _both(args, ct, expert, live, block)
    _same(kernel, plain, "float32")
    # what lies behind the live blocks comes out as zeros
    assert not np.asarray(kernel["out"][live * block:]).any()
    assert not np.asarray(kernel["rows"][live * block:]).any()


@pytest.mark.parametrize("width", [128, 192])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_in_both_dtypes_at_a_small_shape(dtype, width):
    """bfloat16 operands (float32 sums, the float32 cotangent of the last
    product rounded as the MXU rounds it) agree to bfloat16 rounding, at a
    width of whole lanes and at one of a lane group and a half."""
    sizes = [40, 0, 16, 70]
    args, ct, expert, live = _operands(
        jnp.dtype(dtype), 32, 256, width, 4, sizes, n_blocks=8, seed=1)
    kernel, plain = _both(args, ct, expert, live, 32)
    _same(kernel, plain, dtype)


def test_a_chunk_whose_pairs_all_fall_on_one_expert():
    """Six blocks of one expert: one weight tile read, one run summed; every
    other expert's gradient is exactly zero."""
    sizes = [0, 0, 6 * 32 - 5, 0]
    args, ct, expert, live = _operands(
        jnp.float32, 32, 128, 256, 4, sizes, n_blocks=6 + 4, seed=2)
    assert live == 6
    kernel, plain = _both(args, ct, expert, live, 32)
    _same(kernel, plain, "float32")
    for stack in STACKS[1:]:
        got = np.asarray(kernel[stack])
        assert got[2].any() and not got[[0, 1, 3]].any()


def test_an_expert_no_pair_fell_on_gets_a_gradient_of_exact_zeros():
    """No grid step of the blocks visits such an expert's tile: the step the
    grid has for it writes the zeros. First, middle and last expert missed,
    and the last live block's expert followed by a missed one."""
    sizes = [0, 33, 0, 0, 20, 0]
    args, ct, expert, live = _operands(
        jnp.float32, 16, 128, 128, 6, sizes, n_blocks=9, seed=3)
    kernel, plain = _both(args, ct, expert, live, 16)
    _same(kernel, plain, "float32")
    for stack in STACKS[1:]:
        got = np.asarray(kernel[stack])
        assert np.isfinite(got).all()
        assert not got[[0, 2, 3, 5]].any() and got[1].any() and got[4].any()


def test_a_chunk_with_no_live_block_gives_zeros_everywhere():
    """Nothing is multiplied, whatever the rows and the map hold: output and
    every gradient are zeros, finite."""
    args, ct, expert, live = _operands(
        jnp.float32, 16, 128, 128, 4, [0, 0, 0, 0], n_blocks=5, seed=4)
    assert live == 0
    rows = jax.random.normal(jax.random.PRNGKey(5), args[0].shape, jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(6), ct.shape, jnp.float32)
    kernel, _ = _both((rows,) + args[1:], ct, expert, live, 16)
    for what in ("out",) + STACKS:
        got = np.asarray(kernel[what])
        assert np.isfinite(got).all() and not got.any(), what


def test_a_last_block_with_one_live_row():
    """An expert's pairs end one row into its second block: that block is
    live and multiplied whole, its other rows zeros that add nothing."""
    sizes = [0, 16 + 1, 0, 5]
    args, ct, expert, live = _operands(
        jnp.float32, 16, 128, 128, 4, sizes, n_blocks=7, seed=7)
    assert live == 3
    kernel, plain = _both(args, ct, expert, live, 16)
    _same(kernel, plain, "float32")
    out = np.asarray(kernel["out"])
    assert out[16].any() and not out[17:32].any()


def _rows_and_weights(dtype=jnp.float32, rows=64, d=128, width=256, held=4,
                      w_dtype=None):
    return (jax.ShapeDtypeStruct((rows, d), dtype),
            jax.ShapeDtypeStruct((held, d, width), w_dtype or dtype))


@pytest.mark.parametrize("mode, block, shapes, taken", [
    ("mosaic", 16, {}, True),
    ("interpret", 16, {}, True),
    ("xla", 16, {}, False),  # off a TPU: the plain body's, whatever the shapes
    ("mosaic", 128, dict(rows=9216, d=3072, width=1024, held=8), True),
    ("mosaic", 1024, dict(rows=40960, d=2048, width=1536, held=8), True),
    # 14.5 lane groups, 116 sublane tiles: as the output's width and as the
    # contraction
    ("mosaic", 128, dict(rows=9216, d=2688, width=1856, held=8), True),
    ("mosaic", 128, dict(rows=9216, d=1856, width=2688, held=8), True),
    ("mosaic", 16, dict(width=192), True),
    ("mosaic", 16, dict(d=96), False),  # widths under a lane group
    ("mosaic", 16, dict(width=64), False),
    ("mosaic", 16, dict(width=200), False),  # no whole number of sublane tiles
    ("mosaic", 16, dict(d=136), False),
    ("mosaic", 8, {}, False),  # a block of half a bfloat16 tile
    ("mosaic", 48, {}, False),  # a block that does not divide the rows
    ("mosaic", 16, dict(w_dtype=jnp.bfloat16), False),  # a copy would be cast
])
def test_the_body_follows_backend_and_shapes(monkeypatch, mode, block, shapes, taken):
    monkeypatch.setattr(ek, "_mode", lambda interpret: mode)
    assert ek.takes(*_rows_and_weights(**shapes), block) is taken


# What ``_columns`` gave at the parent of PR 49 for a stack ``[held, in, out]``
# (the forward product's tile, the transposed one's, the float32 gradient's),
# for each cell's ``(in, out)`` stacks and its ``(out, in)`` one: a width of
# whole lanes is cut as it was. Nemotron-H's goes whole where 1,856 is the
# width cut (no lane multiple divides it) and in thirds where 2,688 is.
COLUMNS = {
    "laguna": ((1024, 3072, 512), (3072, 1024, 1536)),
    "lfm2": ((1536, 2048, 768), (2048, 1536, 1024)),
    "qwen3_next": ((512, 2048, 512), (2048, 512, 2048)),
    "joyai": ((768, 2048, 768), (2048, 768, 2048)),
    "nemotron": ((1856, 896, 1856), (896, 1856, 896)),
}


@pytest.mark.parametrize("cell", sorted(COLUMNS))
def test_a_cells_tiles_are_the_ones_they_were(cell):
    _, d, width, _ = CELLS[cell]
    for (w_in, w_out), want in zip(((d, width), (width, d)), COLUMNS[cell]):
        assert (ek._columns(w_in, w_out, 2), ek._columns(w_out, w_in, 2),
                ek._columns(w_in, w_out, 4)) == want, (w_in, w_out)
        for cols, whole in zip(want, (w_out, w_in, w_out)):
            assert whole % cols == 0  # the grid is ``width // columns`` tiles


def test_shapes_the_kernels_refuse_are_refused_before_a_trace():
    rows, w = _rows_and_weights(d=96)
    with pytest.raises(ValueError, match="widths of a lane group or more"):
        ek.grouped_product(
            jnp.zeros(rows.shape), jnp.zeros(w.shape), jnp.zeros(4, jnp.int32),
            1, 16, interpret=True)


def _traced(body):
    return get_global_registry().counter(
        lm_layers.PRODUCTS_TRACED, labels={"body": body}).value


@pytest.mark.parametrize("mode, body", [("interpret", "kernel"), ("xla", "plain")])
def test_the_counter_says_which_body_an_expert_layers_products_took(
        monkeypatch, mode, body):
    """``routed_experts`` at lane widths: three products a layer traced,
    through the kernels where the backend says so (here: the test does) and
    through the plain body off a TPU; at widths the kernels refuse, the plain
    body's whatever the backend."""
    monkeypatch.setattr(ek, "_mode", lambda interpret: mode)
    n, held = 24, 4

    def layer(d, width):
        x = jax.ShapeDtypeStruct((n, d), jnp.float32)
        picked = jax.ShapeDtypeStruct((n, held), jnp.bool_)
        gates = jax.ShapeDtypeStruct((n, held), jnp.float32)
        w = [jax.ShapeDtypeStruct((held,) + s, jnp.float32)
             for s in [(d, width), (d, width), (width, d)]]
        return jax.eval_shape(lambda x, g, p, *w: lm_layers.routed_experts(
            x, None, g, p, w, 2, 64, 16), x, gates, picked, *w)

    other = "plain" if body == "kernel" else "kernel"
    before = {b: _traced(b) for b in ("kernel", "plain")}
    y, _, _ = layer(128, 256)
    assert y.shape == (n, 128)
    assert _traced(body) == before[body] + 3 and _traced(other) == before[other]
    before = {b: _traced(b) for b in ("kernel", "plain")}
    layer(64, 32)
    assert _traced("plain") == before["plain"] + 3
    assert _traced("kernel") == before["kernel"]
