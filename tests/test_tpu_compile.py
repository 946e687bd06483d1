"""Deviceless compiles for a described TPU v5e: what the chip's compiler
makes of the hot kernels at their real widths, at no chip time.

One file, one process: only one process at a time may load the TPU's
library, so the topology is described inside a fixture (never at import)
and every compile happens in the test's own process.
"""

import collections
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fedtpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("inverse", [False, True], ids=["forward", "inverse"])
def test_rotation_compiles_to_three_f32_products_and_one_copy(one_chip, inverse):
    """``hadamard_rotate`` at the benchmark cell's ``[192, 2^20]`` row: one
    MXU convolution per Kronecker factor, each at HIGHEST precision in the
    OPTIMIZED module (nothing downgraded it), and at most one layout copy
    of the whole buffer (the 8-rows-at-a-time view is what keeps XLA from
    copying it on the way in and between products)."""
    rows, h = 192, 2**20
    compiled = pk.hadamard_rotate.lower(
        jax.ShapeDtypeStruct((rows, h), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((h,), jnp.float32, sharding=one_chip),
        inverse=inverse,
    ).compile()
    text = compiled.as_text()
    products = re.findall(r" convolution\([^\n]*", text)
    assert len(products) == 3, products
    for line in products:
        assert "operand_precision={highest,highest}" in line, line
    entry = text[text.index("ENTRY"):]
    whole = rows * h
    moves = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = f32\[([0-9,]+)\]\S* (copy|reshape|transpose)\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) == whole:
            moves.append(line.strip()[:120])
    assert len(moves) <= 1, moves
    # one output buffer a product, not the butterfly's ~6x in temporaries
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.1 * whole * 4


def test_the_expert_layer_compiles_at_the_published_widths_with_its_scopes(one_chip):
    """One row of 4,096 tokens through an expert layer that holds 8 of 256
    experts of width 768 (``joyai_llm_flash.fl4_seq4k``'s micro-batch),
    forward and backward, through the PLAIN body (the backend here is the CPU,
    and this test leaves the choice alone; the kernels' compile is
    ``test_the_expert_kernels_compile_at_a_cells_chunk``): the grouped
    products are batched products over
    blocks of 256 rows that carry the program's scope (``jax.lax.ragged_dot``
    would compile to kernels named ``ragged-dot-none``, which a capture reads
    as ``_unscoped_``), no product runs over every expert's copy of the
    tokens, and the layer's temporaries stay under 2 GB."""
    from fedtpu.models import joyai_llm_flash as m, lm_layers

    layer = lm_layers.ExpertLayer(
        **m.experts(m.Sizes(experts_held=(0, 8), moe_chunk_pairs=4096), 1))
    x = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048)))["params"])
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one_chip), params)

    def loss(params, x):
        y, pairs, _ = layer.apply({"params": params}, x)
        return jnp.sum(y.astype(jnp.float32)), pairs

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        params, x).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    grouped = [l for l in text.splitlines() if " convolution(" in l
               and "fed.local_step.fwd_bwd.moe.experts" in l]
    # 3 forward, 6 transposed products a chunk body: the first chunk's and the loop's
    assert len(grouped) >= 9 * 2
    assert not re.search(r"bf16\[8,4096,2048\]|bf16\[8,32768,2048\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_the_attention_layer_compiles_to_one_forward_and_one_backward_kernel(
        one_chip, monkeypatch):
    """One row of 4,096 tokens through latent attention at the published
    widths (32 heads of 128 + 64 and 128), forward and backward under
    ``nn.remat`` with the model's policy, as ``joyai_llm_flash.fl4_seq4k``
    runs a layer. The backend here is the CPU, so the test itself says
    "Mosaic" where the program asks. The core is TWO kernels, one forward (its
    output and log-sum-exp are kept, so the rematerialised forward pass runs
    none) and one backward; both carry the scope the benchmark reads
    (``mla.core_roofline`` divides by the time under it: a kernel outside it
    would read over 100 %); and no float32 tensor of heads x queries x keys
    is left in the module (the plain body's ``f32[32,512,k]`` scores)."""
    from fedtpu.models import joyai_llm_flash as m, lm_layers
    from fedtpu.ops import attention_kernels as ak

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    sizes = m.Sizes()
    layer = lm_layers.rematerialised(m.LatentAttention)(sizes)
    x = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048)))["params"])
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one_chip), params)

    def loss(params, x):
        return jnp.sum(layer.apply({"params": params}, x).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    kernels = [l for l in text.splitlines()
               if " custom-call(" in l and 'custom_call_target="tpu_custom_call"' in l]
    assert sorted(re.search(r"%(latent_attention_core_\w+?)[.\d]* =", l).group(1)
                  for l in kernels) == [
        "latent_attention_core_bwd", "latent_attention_core_fwd"], kernels
    for line in kernels:
        assert ak.SCOPE in re.search(r'op_name="([^"]*)"', line).group(1), line
    heads = sizes.num_attention_heads
    scores = []
    for dims in re.findall(r"f32\[([0-9,]+)\]", text):
        dims = [int(d) for d in dims.split(",")]
        if heads in dims:
            dims.remove(heads)
            if sum(d >= ak.BLOCK for d in dims) >= 2:
                scores.append(dims)
    assert not scores, scores[:5]


def _mixer_gradient_text(one_chip, cls, scope):
    """Compiled text and memory of one of Qwen3-Next's mixers at the
    published widths on one row of 8,192 tokens, forward and backward under
    ``nn.remat`` with the model's policy and under the scope its block gives
    it, as ``qwen3_next_80b_a3b.fl4_seq8k`` runs a layer."""
    from fedtpu.models import lm_layers, qwen3_next as m

    layer = lm_layers.rematerialised(cls(m))(m.Sizes())
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 2048)))["params"])
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one_chip), params)

    def loss(params, x):
        with jax.named_scope(m.SCOPE + scope):
            return jnp.sum(layer.apply({"params": params}, x).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def test_the_delta_net_layer_compiles_at_the_published_widths_with_its_scopes(
        one_chip, monkeypatch):
    """16 key and 32 value heads of 128 over 8,192 tokens in chunks of 64. The
    test says "Mosaic" where the program asks, and the kernels take these
    shapes: the recurrence is TWO kernels, ``gated_delta_rule_fwd`` (its
    output, the chunks' states and inverses are kept, so the rematerialised
    forward pass runs none) and ``gated_delta_rule_bwd``, both under the scope
    ``gdn.core_roofline`` divides by, as is every relayout around them; q and
    k go in heads-major as the program states them, v and the output as
    ``[T, heads x width]``. Nothing of the plain chunks is left: no loop and
    no triangular solve, no product under the core's scope outside the
    kernels, no float32 tensor of a chunk's matrices ``[128, 16, 2, 64, 64]``
    or of the solve's sides ``[128, 16, 2, 64, 256]`` in any order of axes
    (the kernels' own residuals are the states ``[16, 128, 2, 128, 128]`` and
    the inverses, two value heads' side by side, ``[16, 128, 64, 128]``), no
    tensor with a state a token (``[8192, 32, 128, 128]``); every product of
    the layer carries the layer's scope; the layer's temporaries stay under 4
    GB; and no copy that lacks the program's metadata, which a capture reads
    as ``_unscoped_``, turns q or k."""
    from fedtpu.ops import delta_rule_kernels as dr

    monkeypatch.setattr(dr, "_mode", lambda interpret: "mosaic")
    text, temp = _mixer_gradient_text(
        one_chip, lambda m: m.GatedDeltaNet, "linear_attention")
    scope = "fed.local_step.fwd_bwd.linear_attention"
    assert dr.SCOPE == scope + ".core"
    kernels = [l for l in text.splitlines()
               if " custom-call(" in l and 'custom_call_target="tpu_custom_call"' in l]
    assert sorted(re.search(r"%(gated_delta_rule_\w+?)[.\d]* =", l).group(1)
                  for l in kernels) == [
        "gated_delta_rule_bwd", "gated_delta_rule_fwd"], kernels
    for line in kernels:
        assert dr.SCOPE in re.search(r'op_name="([^"]*)"', line).group(1), line
        operands = line[line.index("operand_layout_constraints="):line.index("metadata=")]
        assert operands.count("bf16[16,8192,128]{2,1,0}") == 2, operands
        assert "bf16[8192,4096]{1,0}" in operands and "f32[16,128,4,64]" in operands
    forward = next(l for l in kernels if "gated_delta_rule_fwd" in l)
    assert "f32[16,128,2,128,128]" in forward and "f32[16,128,64,128]" in forward
    assert not [l for l in text.splitlines() if " while(" in l]
    assert "triangular_solve" not in text
    products = [l for l in text.splitlines() if " convolution(" in l]
    assert products and all(scope in l for l in products)
    assert not any(dr.SCOPE in l for l in products)  # the rule's are in the kernels
    gone = [sorted([128, 16, 2, 64, 64]), sorted([128, 16, 2, 64, 256])]
    for kind, dims in re.findall(r"(f32|bf16)\[([0-9,]+)\]", text):
        dims = [int(d) for d in dims.split(",")]
        assert math.prod(dims) < 8192 * 32 * 128 * 128, dims
        assert kind != "f32" or sorted(d for d in dims if d > 1) not in gone, dims
    assert temp < 4e9
    assert not [l for l in text.splitlines() if "op_name=" not in l and re.search(
        r"= (?:bf16|f32)\[(?:1024,8,16,128|8192,16,128|16,8192,128|8192,2048)\]"
        r"\S* copy\(", l)]


def test_the_grouped_softmax_layer_compiles_at_the_published_widths_with_its_scopes(
        one_chip, monkeypatch):
    """16 query heads on 2 key-value heads of 256 over 8,192 tokens. The test
    says "Mosaic" where the program asks, and the kernels take these shapes
    (a key head serves a group of 8, there is no rotary operand): the core is
    TWO kernels named as JoyAI's, one forward (kept, so the rematerialised
    forward pass runs none) and one backward, at ``[16, 8192, 256]`` queries
    on ``[2, 8192, 256]`` keys (a key-value head is read by its group, not
    copied), inside the VMEM limit (the compile would refuse them), both
    under the scope ``full_attention``'s reader and the core's time are read
    by, as are the heads-first relayouts around them; the projections carry
    the layer's scope; and no float32 block of scores ``[., 8, 512, .]`` of
    the plain body is left in the module."""
    from fedtpu.ops import attention_kernels as ak

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    text, temp = _mixer_gradient_text(one_chip, lambda m: m.GatedAttention, "attention")
    scope = "fed.local_step.fwd_bwd.attention"
    assert ak.SCOPE == scope + ".core"
    kernels = [l for l in text.splitlines()
               if " custom-call(" in l and 'custom_call_target="tpu_custom_call"' in l]
    assert sorted(re.search(r"%(latent_attention_core_\w+?)[.\d]* =", l).group(1)
                  for l in kernels) == [
        "latent_attention_core_bwd", "latent_attention_core_fwd"], kernels
    for line in kernels:
        assert ak.SCOPE in re.search(r'op_name="([^"]*)"', line).group(1), line
        operands = line[line.index("operand_layout_constraints="):line.index("metadata=")]
        assert "bf16[16,8192,256]" in operands and "bf16[2,8192,256]" in operands, operands
        used = re.search(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)
        assert 0 < int(used.group(1)) <= ak._VMEM_LIMIT
    products = [l for l in text.splitlines() if " convolution(" in l]
    assert products and all(scope in l for l in products)
    assert not any(ak.SCOPE in l for l in products)  # the core's are in the kernels
    moved = [l for l in text.splitlines() if re.search(
        r"= (?:bf16|f32)\[(?:2,8|16),8192,256\]\S* (?:copy|transpose|fusion)\(", l)]
    assert moved and all(scope in l for l in moved), [l[:200] for l in moved]
    assert not re.findall(r"f32\[(?:\d+,)*8,512,\d+\]", text)
    assert temp < 2e9


def _lfm2_gradient(one_chip, make, scope):
    """Compiled text and temporaries of one of LFM2-MoE's layers
    (``make(sizes)``: its class and its fields) at the published
    widths on ``lfm2_24b_a2b.fl4_b8_seq4k``'s micro-batch, 8 rows of 4,096
    tokens, forward and backward rematerialised as a block's part is
    (``lm_layers.rematerialised``) and under the scope its block gives it."""
    from fedtpu.models import lfm2_moe as m, lm_layers

    cls, fields = make(m.Sizes(experts_held=(0, 8)))
    layer = lm_layers.rematerialised(cls)(**fields)
    x = jax.ShapeDtypeStruct((8, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048)))["params"])
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one_chip), params)

    def loss(params, x):
        with jax.named_scope(m.SCOPE + scope):
            y = layer.apply({"params": params}, x)
        return jnp.sum((y[0] if isinstance(y, tuple) else y).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def test_the_lfm2_softmax_layer_compiles_to_one_forward_and_one_backward_kernel(
        one_chip, monkeypatch):
    """32 query heads on 8 key-value heads of 64, forward and backward under
    the block's remat policy on the cell's micro-batch, a row of 4,096 tokens
    at a time. The test says "Mosaic" where the program asks, and the kernels
    take heads of half a lane group: the core is TWO kernels named as the
    other models', one forward (kept, so the rematerialised forward pass runs
    none) and one backward, a key head's four query heads stacked in a grid
    step (``[8, 16384, 64]`` queries on ``[8, 4096, 64]`` keys: a key-value
    head is read once a step, not copied, and ``dk`` / ``dv`` leave the
    kernel summed over the group, no float32 part a head), inside the VMEM
    limit, both under the scope ``gqa.core_roofline`` divides by (a kernel
    outside it would read over 100 %); the relayouts around them carry the
    layer's scope, the core's where no projection's product absorbs them; and
    no float32 block of scores ``[., 4, 512, k]`` of the plain body is left
    in the module."""
    from fedtpu.models import lfm2_moe as m
    from fedtpu.ops import attention_kernels as ak

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    text, temp = _lfm2_gradient(
        one_chip, lambda sizes: (m.Attention, dict(sizes=sizes)), "attention")
    scope = "fed.local_step.fwd_bwd.attention"
    assert ak.SCOPE == scope + ".core"
    kernels = [l for l in text.splitlines()
               if " custom-call(" in l and 'custom_call_target="tpu_custom_call"' in l]
    assert sorted(re.search(r"%(latent_attention_core_\w+?)[.\d]* =", l).group(1)
                  for l in kernels) == [
        "latent_attention_core_bwd", "latent_attention_core_fwd"], kernels
    for line in kernels:
        assert ak.SCOPE in re.search(r'op_name="([^"]*)"', line).group(1), line
        operands = line[line.index("operand_layout_constraints="):line.index("metadata=")]
        assert "bf16[8,16384,64]" in operands and "bf16[8,4096,64]" in operands, operands
        assert "f32[8,4096,64]" not in line  # no group parts to sum outside
        used = re.search(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)
        assert 0 < int(used.group(1)) <= ak._VMEM_LIMIT
    moved = [l for l in text.splitlines() if re.search(
        r"= bf16\[8,(?:512,8,4|16384|4096),64\]\S* (?:copy|transpose|fusion)\(", l)]
    assert moved and all(scope in l for l in moved), [l[:200] for l in moved]
    assert any(ak.SCOPE in l for l in moved)
    scores = [dims for dims in re.findall(r"f32\[((?:\d+,)*4,512,\d+)\]", text)
              if int(dims.rsplit(",", 1)[1]) >= ak.BLOCK]
    assert not scores, scores[:5]
    assert temp < 2e9


def test_the_short_convolution_compiles_at_the_published_widths_with_its_scopes(one_chip):
    """Hidden 2,048, three taps, 32,768 tokens: ``W_in`` and ``W_out`` and
    their transposes are products under ``short_conv.proj`` and ``.out``; the
    two gates and the taps are elementwise fusions under ``short_conv.core``
    (what ``short_conv.core_roofline`` divides by), no product among them;
    the layer's temporaries stay under 2 GB."""
    from fedtpu.models import lfm2_moe as m

    text, temp = _lfm2_gradient(
        one_chip, lambda sizes: (m.ShortConv, dict(sizes=sizes)), "short_conv")
    scope = "fed.local_step.fwd_bwd.short_conv"
    products = [l for l in text.splitlines() if " convolution(" in l]
    assert products and all(scope in l for l in products)
    named = lambda part: [l for l in products if scope + part in l]
    # the forward product (W_in's once more in the backward pass) and two transposes
    assert len(named(".proj")) >= 3 and len(named(".out")) >= 2
    core = [l for l in text.splitlines() if scope + ".core" in l and " fusion(" in l]
    assert core, "no fusion carries the core's scope"
    assert not named(".core")  # gates and taps are no products
    assert temp < 2e9


def _bytes_and_entry(one_chip, fn, *shapes):
    """Bytes the compiled ``fn`` accesses, and its entry computation's lines:
    what is written to memory is an instruction's output THERE (inside a
    fused computation a value lives in registers)."""
    compiled = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip) for s in shapes)).compile()
    text = compiled.as_text()
    return compiled.cost_analysis()["bytes accessed"], text[text.index("ENTRY"):].splitlines()


def test_the_causal_convolution_moves_its_operand_once_forward_and_once_backward(one_chip):
    """``lm_layers.causal_conv`` alone with each caller's elementwise
    neighbours, bfloat16. The hybrid's ``[8192, 8192]`` under four taps and
    SiLU: the forward is one read and one write of the operand (0.269 GB; the
    form left to ``jax.grad`` read 1.611), ``jax.grad`` with its forward
    0.672 GB (4.162), and nothing the size of the operand is written in
    float32, padded or not (the old form wrote one ``f32[8195, 8192]`` forward
    and four ``f32[8192, 8192]`` backward). LFM2's ``[8, 4096, 2048]`` double
    gate under three taps, vmapped over rows: ``jax.grad`` 3.088 GB (3.624; the
    float32 ``B x X`` that remains is the caller's)."""
    from fedtpu.models import lfm2_moe, lm_layers

    weighed = lambda y, dy: jnp.sum(y.astype(jnp.float32) * dy.astype(jnp.float32))
    act = lambda x, taps: jax.nn.silu(lm_layers.causal_conv(x, taps))
    rows, taps = (8192, 8192), (4, 8192)
    forward, entry = _bytes_and_entry(one_chip, act, rows, taps)
    both, entry_grad = _bytes_and_entry(
        one_chip, jax.grad(lambda x, taps, dy: weighed(act(x, taps), dy), argnums=(0, 1)),
        rows, taps, rows)
    assert forward < 0.35e9, forward
    assert both < 0.9e9, both
    written = [l.strip()[:120] for l in entry + entry_grad
               if re.search(r"= [^=]*f32\[819[25],8192\][^=]* (?!parameter)\w+\(", l)]
    assert not written, written

    gated = jax.vmap(lfm2_moe.gated_short_conv, in_axes=(0, 0, 0, None))
    rows = (8, 4096, 2048)
    lfm2, _ = _bytes_and_entry(
        one_chip, jax.grad(lambda b, c, x, taps, dy: weighed(gated(b, c, x, taps), dy),
                           argnums=(0, 1, 2, 3)), rows, rows, rows, (3, 2048), rows)
    assert lfm2 < 3.4e9, lfm2


def test_the_lfm2_expert_layer_compiles_at_a_deployments_rows_a_product(one_chip):
    """8 of 64 experts of width 1,536 on a micro-batch of 32,768 tokens, 4 a
    token: a held expert's expected 2,048 pairs are two blocks of 1,024 rows,
    the grouped products (the plain body's: the backend here is the CPU) are
    batched products over a chunk's 32 + 8 blocks
    under ``fed.local_step.fwd_bwd.moe.experts`` (what
    ``moe.experts_device_share`` reads), no ``ragged-dot`` kernel without the
    program's scope, no product over every expert's copy of the tokens, no
    tensor of zeros stands in for the shared expert this model lacks, and the
    layer's temporaries stay under 5 GB."""
    from fedtpu.models import lfm2_moe as m, lm_layers

    text, temp = _lfm2_gradient(
        one_chip, lambda sizes: (lm_layers.ExpertLayer, m.experts(sizes, 2)), "moe")
    assert "ragged-dot" not in text
    grouped = [l for l in text.splitlines() if " convolution(" in l
               and "fed.local_step.fwd_bwd.moe.experts" in l]
    # 3 forward, 6 transposed products a chunk body: the first chunk's and the loop's
    assert len(grouped) >= 9 * 2
    assert any("bf16[40,1024,2048]" in l or "bf16[40,1024,1536]" in l for l in grouped)
    assert not re.search(r"bf16\[8,32768,2048\]|bf16\[8,131072,2048\]", text)
    assert temp < 5e9


def _kernel_lines(text):
    return [l for l in text.splitlines()
            if " custom-call(" in l and 'custom_call_target="tpu_custom_call"' in l]


EXPERT_CELLS = {  # (block, in, out, held, blocks a chunk)
    "laguna_s_2_1.fl4_seq8k": (128, 3072, 1024, 8, 72),
    "lfm2_24b_a2b.fl4_b8_seq4k": (1024, 2048, 1536, 8, 40),
    "qwen3_next_80b_a3b.fl4_seq8k": (128, 2048, 512, 16, 80),
    "joyai_llm_flash.fl4_seq4k": (256, 2048, 768, 8, 24),
    # 1,856 = 14.5 lane groups: the output's width of two products and the
    # contraction of the third, and the other way round
    "nemotron_3_nano_30b_a3b.fl4_seq8k": (128, 2688, 1856, 8, 72),
    "nemotron_3_nano_30b_a3b.fl4_seq8k.down": (128, 1856, 2688, 8, 72),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_the_expert_kernels_compile_at_a_cells_chunk(one_chip, cell):
    """An expert layer's three grouped products and their gradients on one
    chunk of a language cell (its block, widths, held experts and blocks a
    chunk, bfloat16) through ``fedtpu/ops/expert_kernels.py`` with Mosaic
    asked for: nine kernels (three forward products, three on the transposed
    contraction, three weight gradients), each inside the VMEM limit, the
    backward ones under the scope the rule names itself; the weights go in as
    the stacks they are (no ``[blocks, in, out]`` copy of a block's expert
    anywhere) and the temporaries stay under a gigabyte."""
    from fedtpu.ops import expert_kernels as ek

    block, d, width, held, n_blocks = EXPERT_CELLS[cell]
    of = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(rows, w_gate, w_up, w_down, expert, live):
        product = lambda x, w, dtype=None: ek.grouped_product(
            x, w, expert, live, block, dtype, interpret=False)
        hidden = jax.nn.silu(product(rows, w_gate)) * product(rows, w_up)
        return jnp.sum(product(hidden, w_down, jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        of(jnp.bfloat16, n_blocks * block, d), of(jnp.bfloat16, held, d, width),
        of(jnp.bfloat16, held, d, width), of(jnp.bfloat16, held, width, d),
        of(jnp.int32, n_blocks), of(jnp.int32)).compile()
    text = compiled.as_text()
    kernels = [(re.search(r"%(expert_\w+?)[.\d]* =", l).group(1), l)
               for l in _kernel_lines(text)]
    assert collections.Counter(name for name, _ in kernels) == {
        "expert_product": 3, "expert_product_transposed": 3,
        "expert_weights_gradient": 3}, kernels
    for name, line in kernels:
        used = re.search(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)
        assert 0 < int(used.group(1)) <= ek._VMEM_LIMIT
        if name != "expert_product":  # the forward's scope is its caller's
            assert ek.SCOPE in re.search(r'op_name="([^"]*)"', line).group(1), line
    assert not re.search(
        rf"\[{n_blocks},(?:{d},{width}|{width},{d})\]", text)  # a block's copy
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def _laguna_gradient(one_chip, layer):
    """Compiled text and temporaries of one of Laguna's attention layers (the
    published layer ``layer``: 0 is full, 1 sliding) at the published widths
    and this chip's share of the heads (one key-value head of 128 with its 6
    or 9 query heads) on ``laguna_s_2_1.fl4_seq8k``'s micro-batch, one row of
    8,192 tokens, forward and backward under ``nn.remat`` with the model's
    policy and under the scope its block gives it."""
    from fedtpu.models import laguna as m, lm_layers

    sizes = m.Sizes(kv_heads_held=(0, 1))
    scope = "window_attention" if sizes.kind(layer) == m.KINDS[1] else "attention"
    mixer = lm_layers.rematerialised(m.Attention)(sizes, layer)
    x = jax.ShapeDtypeStruct((1, 8192, 3072), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 3072)))["params"])
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one_chip), params)

    def loss(params, x):
        with jax.named_scope(m.SCOPE + scope):
            return jnp.sum(mixer.apply({"params": params}, x).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def test_the_laguna_full_layer_compiles_to_one_forward_and_one_backward_kernel(
        one_chip, monkeypatch):
    """6 query heads on ONE key-value head of 128 over 8,192 tokens, YaRN on
    half a head and a gate a head. The test says "Mosaic" where the program
    asks, and the kernels take this share of the heads as they take whole
    layers: the core is TWO kernels named as the other models', one forward
    (kept, so the rematerialised forward pass runs none) and one backward, at
    ``[6, 8192, 128]`` queries on ``[1, 8192, 128]`` keys, inside the VMEM
    limit, both under the scope ``window_attention.full_core_roofline``
    divides by; projections, gate and ``W_o`` carry the layer's scope and not
    the core's; no float32 block of scores ``[., 6, 512, k]`` of the plain
    body is left in the module."""
    from fedtpu.ops import attention_kernels as ak

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    text, temp = _laguna_gradient(one_chip, 0)
    scope = "fed.local_step.fwd_bwd.attention"
    kernels = _kernel_lines(text)
    assert sorted(re.search(r"%(latent_attention_core_\w+?)[.\d]* =", l).group(1)
                  for l in kernels) == [
        "latent_attention_core_bwd", "latent_attention_core_fwd"], kernels
    for line in kernels:
        assert ak.SCOPE in re.search(r'op_name="([^"]*)"', line).group(1), line
        operands = line[line.index("operand_layout_constraints="):line.index("metadata=")]
        assert "bf16[6,8192,128]" in operands and "bf16[1,8192,128]" in operands, operands
        used = re.search(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)
        assert 0 < int(used.group(1)) <= ak._VMEM_LIMIT
    products = [l for l in text.splitlines() if " convolution(" in l]
    assert products and all(scope in l for l in products)
    assert not any(ak.SCOPE in l for l in products)  # the core's are in the kernels
    turns = [l for l in text.splitlines() if " cosine(" in l or " sine(" in l]
    assert turns and all(scope in l and ak.SCOPE not in l for l in turns)  # YaRN's
    assert "window_attention" not in text
    scores = [dims for dims in re.findall(r"f32\[((?:\d+,)*6,512,\d+)\]", text)
              if int(dims.rsplit(",", 1)[1]) >= ak.BLOCK]
    assert not scores, scores[:5]
    assert temp < 2e9


def test_the_laguna_sliding_layer_compiles_to_banded_plain_blocks_with_its_scopes(
        one_chip, monkeypatch):
    """9 query heads on ONE key-value head of 128 over 8,192 tokens, window
    512, plain rotary over the whole head. The test says "Mosaic" where the
    program asks and the kernels still refuse a windowed call: no custom call
    in the module. The core is the plain query blocks cut to the band, under
    ``fed.local_step.fwd_bwd.window_attention.core`` (what
    ``window_attention.core_roofline`` divides by): a block of 512 queries
    meets at most ``512 + 511`` keys, so no float32 scores wider than 1,023
    keys exist (the causal body's widest are 8,192); the projections, the
    gate, the rotary turns and ``W_o`` carry the layer's scope and not the
    core's; nothing carries the full layers' scope."""
    from fedtpu.ops import attention_kernels as ak

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    text, temp = _laguna_gradient(one_chip, 1)
    scope = "fed.local_step.fwd_bwd.window_attention"
    assert not _kernel_lines(text)
    products = [l for l in text.splitlines() if " convolution(" in l]
    core = [l for l in products if scope + ".core" in l]
    assert products and all(scope in l for l in products)
    assert core and len(products) - len(core) >= 5 * 3 - 1  # five projections
    # the rotary turns too: the core's seconds are scores, softmax and ``P v``
    turns = [l for l in text.splitlines() if " cosine(" in l or " sine(" in l]
    assert turns and all(scope in l and scope + ".core" not in l for l in turns)
    assert "fwd_bwd.attention" not in text
    keys = [int(dims.rsplit(",", 1)[1])
            for dims in re.findall(r"f32\[((?:\d+,)*9,512,\d+)\]", text)]
    assert keys and max(keys) == 1023 and min(keys) == 512, sorted(set(keys))
    assert temp < 2e9


def test_the_laguna_round_program_fits_one_chip_at_the_cells_size(one_chip, monkeypatch):
    """``laguna_s_2_1.fl4_seq8k``'s whole round program from its own files (4
    clients in sequence, 2 steps of 2 rows of 8,192 tokens in micro-batches of
    one row, plain SGD on bfloat16 over the 568 M float32 masters, mixer and
    feed-forward rematerialised each by itself), from shapes alone, compiled
    for one described v5e with the kernels as the chip would choose them: the
    chip's compiler refuses a program that does not fit its memory, so the
    compile IS the check (14.60 GB "Total bytes used" in its memory report at
    PR 44, 10.60 GB since PR 45 took the per-block copies of the experts'
    weights and the chain of conditionals a chunk out, of the 15.75 GiB a
    chip gives; PERF.md section 6). The two
    full layers' cores are two kernels each, forward and backward, the three
    sliding layers' are plain blocks under their own scope, the four sparse
    layers' grouped products are the expert kernels, chunk by chunk, and
    every scope the cell's readers read is in the module."""
    from benchmark import run as bench, sut
    from fedtpu import models
    from fedtpu.core.round import init_state
    from fedtpu.data.device import make_data_round_step
    from fedtpu.ops import attention_kernels as ak
    from fedtpu.ops import expert_kernels as ek

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    monkeypatch.setattr(ek, "_mode", lambda interpret: "mosaic")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = bench.Cell(os.path.join(root, "BENCHMARK.json"), "laguna_s_2_1.fl4_seq8k")
    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    t, clients = cell.config["seq_len"], cell.traffic["clients"]
    shard = cell.config["rows_per_client"]
    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat,
                          **dict(cfg.model_args))
    state = jax.eval_shape(
        lambda key: init_state(model, cfg, key, jnp.zeros((1, t), jnp.int32)),
        jax.random.PRNGKey(0))
    assert sum(math.prod(l.shape) for l in jax.tree.leaves(state.params)) == 567_957_504
    step = jax.jit(make_data_round_step(
        model, cfg, cfg.steps_per_round, shuffle=False, image_shape=(t,),
        layout="gather"), donate_argnums=(0,))
    shapes = (
        state, jnp.zeros((clients * shard, t), jnp.int32),
        jnp.zeros((clients * shard, t), jnp.int32),
        jnp.zeros((clients, shard), jnp.int32), jnp.ones((clients, shard), bool),
        jnp.ones((clients,), jnp.float32), jnp.ones((clients,), bool),
        jax.random.PRNGKey(0))
    shapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), shapes)
    text = step.lower(*shapes).compile().as_text()  # raises where it does not fit
    kernels = collections.Counter(
        re.search(r"%(\w+?)[.\d]* =", l).group(1) for l in _kernel_lines(text))
    # a sparse layer's chunk bodies: the first chunk and the loop's, each
    # forward and again where the backward pass makes the rows anew
    bodies = 4 * 2
    assert kernels == {
        "latent_attention_core_fwd": 2, "latent_attention_core_bwd": 2,
        "expert_product": 3 * 2 * bodies,
        "expert_product_transposed": 3 * bodies,
        "expert_weights_gradient": 3 * bodies}, kernels
    assert not re.search(r"bf16\[72,(?:3072,1024|1024,3072)\]", text)  # a block's copy
    # no chain of conditionals a chunk: none hands the stacks through a
    # branch as a copy or fills a skipped chunk's gradients with zeros
    assert " conditional(" not in text
    assert not re.search(r"= bf16\[8,(?:3072,1024|1024,3072)\]\S* (?:copy|broadcast)\(", text)
    pre = "fed.local_step.fwd_bwd."
    for scope in ("window_attention", "window_attention.core", "attention",
                  "attention.core", "dense_ffn", "moe.router", "moe.dispatch",
                  "moe.experts", "moe.combine", "embed", "lm_loss"):
        assert pre + scope + "/" in text, scope
    assert "ragged-dot" not in text


def test_the_nemotron_round_program_fits_one_chip_at_the_cells_size(
        one_chip, monkeypatch, caplog):
    """``nemotron_3_nano_30b_a3b.fl4_seq8k``'s whole round program from its own
    files (4 clients in sequence, 2 steps of 2 rows of 8,192 tokens in
    micro-batches of one row, plain SGD on bfloat16 over the 528 M float32
    masters, every layer's one half rematerialised), from shapes alone,
    compiled for one described v5e with the bodies as the chip would choose
    them: the chip's compiler refuses a program that does not fit its memory,
    so the compile IS the check (11.66 GB "Total bytes used" in its memory
    report at PR 48 with the plain grouped products, 10.56 GB since PR 49 took
    the per-block copies of the experts' weights out, of the 15.75 GiB a chip
    gives). The attention layer's core is two kernels, forward and backward,
    at 16 query heads a key-value head; the three expert layers' grouped
    products are the expert kernels at the published width 1,856, which is no
    whole number of lanes (two products a layer counted under ``kernel``, none
    under ``plain``, no warning, no block's copy of an expert's matrix); the
    three Mamba-2 layers' recurrences are the scan's two kernels since PR 50,
    one forward and one backward a layer (the output and the chunks' starting
    states are kept, so the rematerialised forward pass runs none), both under
    the scope ``mamba.core_roofline`` divides by, and no float32 tensor of a
    chunk's decay matrices ``[64, 8, 8, 128, 128]`` is left in any order of
    axes; ``init_state`` traces the model on eight tokens, which the chunk
    does not divide, so its three cores are the plain chunks'; and every scope
    the cell's readers read is in the module."""
    from benchmark import run as bench, sut
    from fedtpu import models
    from fedtpu.core.round import init_state
    from fedtpu.data.device import make_data_round_step
    from fedtpu.models import lm_layers, mamba2
    from fedtpu.obs.registry import get_global_registry
    from fedtpu.ops import attention_kernels as ak
    from fedtpu.ops import expert_kernels as ek
    from fedtpu.ops import ssd_kernels as sk

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    monkeypatch.setattr(ek, "_mode", lambda interpret: "mosaic")
    monkeypatch.setattr(sk, "_mode", lambda interpret: "mosaic")
    monkeypatch.setattr(lm_layers, "_PLAIN_WIDTHS_WARNED", set())
    counted = lambda name, body: get_global_registry().counter(
        name, labels={"body": body}).value
    before = (counted(lm_layers.PRODUCTS_TRACED, "plain"),
              counted(lm_layers.PRODUCTS_TRACED, "kernel"),
              counted(mamba2.SSD_CORES_TRACED, "plain"),
              counted(mamba2.SSD_CORES_TRACED, "kernel"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = bench.Cell(os.path.join(root, "BENCHMARK.json"),
                      "nemotron_3_nano_30b_a3b.fl4_seq8k")
    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    t, clients = cell.config["seq_len"], cell.traffic["clients"]
    shard = cell.config["rows_per_client"]
    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat,
                          **dict(cfg.model_args))
    state = jax.eval_shape(
        lambda key: init_state(model, cfg, key, jnp.zeros((1, t), jnp.int32)),
        jax.random.PRNGKey(0))
    assert sum(math.prod(l.shape) for l in jax.tree.leaves(state.params)) == 528_092_736
    step = jax.jit(make_data_round_step(
        model, cfg, cfg.steps_per_round, shuffle=False, image_shape=(t,),
        layout="gather"), donate_argnums=(0,))
    shapes = (
        state, jnp.zeros((clients * shard, t), jnp.int32),
        jnp.zeros((clients * shard, t), jnp.int32),
        jnp.zeros((clients, shard), jnp.int32), jnp.ones((clients, shard), bool),
        jnp.ones((clients,), jnp.float32), jnp.ones((clients,), bool),
        jax.random.PRNGKey(0))
    shapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), shapes)
    text = step.lower(*shapes).compile().as_text()  # raises where it does not fit
    kernels = collections.Counter(
        re.search(r"%(\w+?)[.\d]* =", l).group(1) for l in _kernel_lines(text))
    # an expert layer's chunk bodies: the first chunk and the loop's, each
    # forward and again where the backward pass makes the rows anew
    bodies = 3 * 2
    assert kernels == {
        "latent_attention_core_fwd": 1, "latent_attention_core_bwd": 1,
        "expert_product": 2 * 2 * bodies,
        "expert_product_transposed": 2 * bodies,
        "expert_weights_gradient": 2 * bodies,
        "selective_scan_fwd": 3, "selective_scan_bwd": 3}, kernels
    for line in _kernel_lines(text):
        if "%selective_scan_" in line:  # where the roofline's reader finds them
            assert sk.SCOPE + "/" in re.search(r'op_name="([^"]*)"', line).group(1)
    for dims in re.findall(r"f32\[([0-9,]+)\]", text):
        assert sorted(int(d) for d in dims.split(",")) != [8, 8, 64, 128, 128], dims
    assert not re.search(r"bf16\[72,(?:2688,1856|1856,2688)\]", text)  # a block's copy
    # init_state's trace and the round program's: 3 expert layers x 2 stacks,
    # twice; 3 state-space layers, the kernels in the round program's 8,192
    # tokens and the plain chunks in init_state's eight
    assert counted(lm_layers.PRODUCTS_TRACED, "kernel") - before[1] == 2 * 3 * 2
    assert counted(lm_layers.PRODUCTS_TRACED, "plain") == before[0]
    assert counted(mamba2.SSD_CORES_TRACED, "kernel") - before[3] == 3
    assert counted(mamba2.SSD_CORES_TRACED, "plain") - before[2] == 3
    assert not [r.getMessage() for r in caplog.records if r.name == lm_layers.__name__]
    pre = "fed.local_step.fwd_bwd."
    for scope in ("mamba.proj", "mamba.conv", "mamba.core", "mamba.out", "attention",
                  "attention.core", "moe.router", "moe.dispatch", "moe.experts",
                  "moe.combine", "embed", "lm_loss"):
        assert pre + scope + "/" in text, scope
    assert "ragged-dot" not in text and "linear_attention" not in text


def test_the_granite_round_program_fits_one_chip_at_the_cells_size(
        one_chip, monkeypatch, caplog):
    """``granite_4_0_h_micro.fl4_seq8k``'s whole round program from its own
    files (4 clients in sequence, 2 steps of 2 rows of 8,192 tokens in
    micro-batches of one row, plain SGD on bfloat16 over the 653 M float32
    masters, each half of each layer rematerialised by itself), from shapes
    alone, compiled for one described v5e with the bodies as the chip would
    choose them: the chip's compiler refuses a program that does not fit its
    memory, so the compile IS the check (12.29 GB "Total bytes used" in its
    memory report at PR 51, step 0 of ISSUE 51, of the 15.75 GiB a chip
    gives: two chips a layer stand). The attention layer's core is the two
    fused kernels at 4 query heads a key-value head of 64 with no rotary
    operand and the config's own scale; the nine Mamba-2 layers' recurrences
    are the PLAIN chunks, in the round program's 8,192 tokens too, because the
    published chunk of 256 is not the one the scan's kernels are built for,
    and ONE warning says so, naming the chunk and the length; no expert
    product is traced; and every scope the cell's readers read is in the
    module."""
    import logging

    from benchmark import run as bench, sut
    from fedtpu import models
    from fedtpu.core.round import init_state
    from fedtpu.data.device import make_data_round_step
    from fedtpu.models import lm_layers, mamba2
    from fedtpu.obs.registry import get_global_registry
    from fedtpu.ops import attention_kernels as ak
    from fedtpu.ops import ssd_kernels as sk

    monkeypatch.setattr(ak, "_mode", lambda interpret: "mosaic")
    monkeypatch.setattr(sk, "_mode", lambda interpret: "mosaic")
    monkeypatch.setattr(mamba2, "_PLAIN_SCANS_WARNED", set())
    counted = lambda name, body: get_global_registry().counter(
        name, labels={"body": body}).value
    names = ((mamba2.SSD_CORES_TRACED, "plain"), (mamba2.SSD_CORES_TRACED, "kernel"),
             (lm_layers.CORES_TRACED, "plain"), (lm_layers.CORES_TRACED, "kernel"),
             (lm_layers.PRODUCTS_TRACED, "plain"), (lm_layers.PRODUCTS_TRACED, "kernel"))
    before = [counted(*n) for n in names]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = bench.Cell(os.path.join(root, "BENCHMARK.json"),
                      "granite_4_0_h_micro.fl4_seq8k")
    cfg = sut.round_config(cell.config, cell.traffic, cell.task)
    t, clients = cell.config["seq_len"], cell.traffic["clients"]
    shard = cell.config["rows_per_client"]
    model = models.create(cfg.model, num_classes=cfg.num_classes, remat=cfg.remat,
                          **dict(cfg.model_args))
    with caplog.at_level(logging.WARNING, logger=mamba2.__name__):
        state = jax.eval_shape(
            lambda key: init_state(model, cfg, key, jnp.zeros((1, t), jnp.int32)),
            jax.random.PRNGKey(0))
        assert sum(math.prod(l.shape) for l in jax.tree.leaves(state.params)) == 652_970_080
        step = jax.jit(make_data_round_step(
            model, cfg, cfg.steps_per_round, shuffle=False, image_shape=(t,),
            layout="gather"), donate_argnums=(0,))
        shapes = (
            state, jnp.zeros((clients * shard, t), jnp.int32),
            jnp.zeros((clients * shard, t), jnp.int32),
            jnp.zeros((clients, shard), jnp.int32), jnp.ones((clients, shard), bool),
            jnp.ones((clients,), jnp.float32), jnp.ones((clients,), bool),
            jax.random.PRNGKey(0))
        shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), shapes)
        text = step.lower(*shapes).compile().as_text()  # raises where it does not fit
    kernels = collections.Counter(
        re.search(r"%(\w+?)[.\d]* =", l).group(1) for l in _kernel_lines(text))
    assert kernels == {"latent_attention_core_fwd": 1, "latent_attention_core_bwd": 1}
    # init_state's trace (eight tokens: plain everywhere) and the round
    # program's: nine scans, plain both times; the one softmax core plain in
    # init_state and the kernels in the round program; no expert product
    after = [counted(*n) for n in names]
    assert [a - b for a, b in zip(after, before)] == [18, 0, 1, 1, 0, 0]
    said = [r.getMessage() for r in caplog.records if r.name == mamba2.__name__]
    assert len(said) == 1 and "256" in said[0] and "8192" in said[0]
    pre = "fed.local_step.fwd_bwd."
    for scope in ("mamba.proj", "mamba.conv", "mamba.core", "mamba.out", "attention",
                  "attention.core", "dense_ffn", "embed", "lm_loss"):
        assert pre + scope + "/" in text, scope
    assert pre + "moe" not in text and "ragged-dot" not in text
