"""Compiles the main path's Pallas kernels for a described TPU v5e device.

No chip is attached: the TPU compiler installed in this image compiles for
a device that `jax.experimental.topologies` describes (the
``on-chip-measurement`` guide, section 2, third rehearsal). Interpret-mode
parity tests cannot see what Mosaic refuses -- an unsupported shape cast, a
VMEM overflow -- and both had shipped in ``ops/pallas_dep_graph.py`` before
this file existed. Every kernel `ops.impl_select.resolve_impl` hands to the
chip by default is compiled here at the shapes ``chip_smoke.py`` runs,
forward and gradient, and must contain ``tpu_custom_call``.

The topology is described inside a module-scoped fixture (never at import,
in a ``skipif`` or a ``parametrize``): only the xdist worker that is given
this file loads libtpu. Keep every such test in THIS file.
"""

import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.pallas

# chip_smoke.py's shapes: the NA tutorial shape (B*L = 32*256 rows of a
# three-level dependency graph, 4 heads of 64) and the width-1024 head
# geometry (8 heads of 128); the synthetic cohort's unified vocabulary.
DEP_GRAPH_SHAPES = {"tutorial_4x64": (8192, 3, 4, 4, 64), "wide_8x128": (8192, 3, 4, 8, 128)}
VOCAB = 4057
# what `ops.pallas_flash.flash_block_sizes` gives at the cells' head widths (rows, heads, chunk_q, chunk_k)
FLASH_SIZES_D128 = (1, 8, 256, 256)
FLASH_SIZES_D256 = (1, 10, 128, 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True, scope="module")
def _chip_compile_config():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep these compiles out of it.
    And compile what the chip compiles: tests/conftest.py forces
    ``jax_default_matmul_precision=highest`` for CPU numerics, which the
    upstream flash kernel's bf16 matmuls inherit and Mosaic then refuses."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("shape_name", list(DEP_GRAPH_SHAPES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_dep_graph_attention(one_chip, shape_name, dtype, direction):
    from eventstreamgpt_tpu.ops.pallas_dep_graph import dep_graph_attention_pallas

    N, Q, S, H, D = DEP_GRAPH_SHAPES[shape_name]
    q = jax.ShapeDtypeStruct((N, Q, H, D), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((N, S, H, D), dtype, sharding=one_chip)

    def fwd(q_, k_, v_):
        return dep_graph_attention_pallas(q_, k_, v_, q_offset=S - Q)

    def grad(q_, k_, v_):
        loss = lambda *a: (fwd(*a).astype(jnp.float32) ** 2).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

    text = _compile(fwd if direction == "forward" else grad, q, kv, kv)
    assert ("dep_graph_attention_bwd" in text) == (direction == "gradient")


def test_dep_graph_attention_with_dropout_mask(one_chip):
    from eventstreamgpt_tpu.ops.pallas_dep_graph import dep_graph_attention_pallas

    N, Q, S, H, D = DEP_GRAPH_SHAPES["tutorial_4x64"]
    q = jax.ShapeDtypeStruct((N, Q, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((N, S, H, D), jnp.bfloat16, sharding=one_chip)
    keep = jax.ShapeDtypeStruct((N, Q, S, H), jnp.bool_, sharding=one_chip)

    def grad(q_, k_, v_, m_):
        def loss(*a):
            out = dep_graph_attention_pallas(
                *a, q_offset=S - Q, dropout_mask=m_, dropout_rate=0.1
            )
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

    _compile(grad, q, kv, kv, keep)


@pytest.mark.parametrize(
    "filters", [{}, {"top_k": 10, "top_p": 0.9}], ids=["plain", "topk_topp"]
)
def test_fused_categorical(one_chip, filters):
    from eventstreamgpt_tpu.ops.fused_sampling import fused_categorical

    logits = jax.ShapeDtypeStruct((8, VOCAB), jnp.bfloat16, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    _compile(lambda z, k: fused_categorical(z, k, impl="pallas", **filters), logits, key)


@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_vocab_gather(one_chip, direction):
    from eventstreamgpt_tpu.ops.pallas_heads import vocab_gather

    z = jax.ShapeDtypeStruct((8, 1024, VOCAB), jnp.bfloat16, sharding=one_chip)
    ci = jax.ShapeDtypeStruct((8, 1024, 24), jnp.int32, sharding=one_chip)
    fwd = lambda z_, ci_: vocab_gather(z_, ci_, impl="pallas")  # noqa: E731
    grad = lambda z_, ci_: jax.grad(lambda a: fwd(a, ci_).sum())(z_)  # noqa: E731
    _compile(fwd if direction == "forward" else grad, z, ci)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_embedding_bag_plane(one_chip, monkeypatch, direction, dtype):
    """The benchmark cells' event embedding (16,384 slots of 24 measurements,
    width 1024): the plane kernel, and no gather of ``(N, M, D)`` rows."""
    from eventstreamgpt_tpu.ops import embedding_bag

    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas")
    n, m, d = 16384, 24, 1024
    table = jax.ShapeDtypeStruct((VOCAB, d), dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((n, m), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((n, m), dtype, sharding=one_chip)
    fwd = lambda t, i, w_: embedding_bag(t, i, w_)  # noqa: E731
    grad = lambda t, i, w_: jax.grad(  # noqa: E731
        lambda a: fwd(a, i, w_).astype(jnp.float32).sum()
    )(t)
    text = _compile(fwd if direction == "forward" else grad, table, idx, w)
    assert "_multihot_2d" in text
    assert f"[{n},{m},{d}]" not in text


@pytest.mark.parametrize("program", ["rows_sharded", "rows_replicated", "generate"])
def test_embedding_bag_plane_over_four_chips(topo, monkeypatch, program):
    """GSPMD refuses a Mosaic call in a program over several chips, so one
    that holds the plane's kernel is traced inside `kernel_mesh`, where the
    kernel runs once per batch shard, or whole on every chip for rows that
    do not divide (an engine's replicated prefill group). ``generate``: the
    whole program of `generate(mesh=...)` for a CI model of width 256."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from eventstreamgpt_tpu.ops import embedding_bag
    from eventstreamgpt_tpu.parallel import kernel_mesh

    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas")
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    whole = NamedSharding(mesh, P())
    by_row = lambda x: NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))  # noqa: E731
    sds = lambda x, sharding: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)  # noqa: E731
    if program == "generate":
        from __graft_entry__ import _make_model_and_batch
        from eventstreamgpt_tpu.generation.generation_utils import _build_ci_steps

        model, batch = _make_model_and_batch(batch_size=8, seq_len=8, hidden=256, vocab=512)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), batch))
        args = (
            jax.tree_util.tree_map(lambda x: sds(x, whole), params),
            jax.tree_util.tree_map(lambda x: sds(x, by_row(x)), batch),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole),
        )
        build = lambda: _build_ci_steps(model, model.config, 8, 8, 2)["generate_program"]  # noqa: E731
    else:
        n = 4096 if program == "rows_sharded" else 6
        idx = jax.ShapeDtypeStruct((n, 24), jnp.int32)
        idx = sds(idx, by_row(idx) if program == "rows_sharded" else whole)
        args = (
            jax.ShapeDtypeStruct((VOCAB, 1024), jnp.bfloat16, sharding=whole),
            idx,
            jax.ShapeDtypeStruct((n, 24), jnp.bfloat16, sharding=idx.sharding),
        )
        build = lambda: jax.jit(lambda *a: embedding_bag(*a))  # noqa: E731
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        build().lower(*args).compile()
    with kernel_mesh(mesh):
        text = build().lower(*args).compile().as_text()
    assert "_multihot_2d" in text
    # The heads' label plane (255 columns of `lab`: over the least width) is
    # traced and dead: nothing of a sampling program reads labels.
    assert "_anyhot" not in text


def _flash_compile(one_chip, B, H, S, D, scale, direction="gradient"):
    """`ops.pallas_flash.flash_attention` in the projections' layout
    ``(B, S, H, D)`` with the sizes it picks, compiled for the chip."""
    from eventstreamgpt_tpu.ops.pallas_flash import flash_attention

    qkv = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)

    def fwd(q, k, v, s):
        with jax.named_scope("layer"):  # a model's modules stand around the op's scope
            return flash_attention(q, k, v, s, sm_scale=scale)

    def grad(q, k, v, s):
        loss = lambda *a: fwd(*a, s).astype(jnp.float32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return _compile(fwd if direction == "forward" else grad, qkv, qkv, qkv, seg)


@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_flash_attention_s1024_d128(one_chip, direction):
    """The flash op of models/transformer.py's ``use_pallas`` branch with
    the sizes it picks at S = 1024, D = 128 (B = 8, 8 heads)."""
    from eventstreamgpt_tpu.ops.pallas_flash import flash_block_sizes

    assert flash_block_sizes(8, 1024, 8, 128) == FLASH_SIZES_D128
    _flash_compile(one_chip, 8, 8, 1024, 128, 1.0, direction)


def test_flash_attention_s1024_d256(one_chip):
    """Latent attention's core (`models/latent_attention.py`): 20 heads of
    256 at S = 1024, 16 rows. A grid step holds ten heads of a row: q, k, v,
    do and the two gradients, 5 MiB each, are 60 MiB double-buffered in the
    dkv kernel, inside the 100 MiB the calls ask for."""
    from eventstreamgpt_tpu.ops.pallas_flash import flash_block_sizes

    assert flash_block_sizes(16, 1024, 20, 256) == FLASH_SIZES_D256
    _flash_compile(one_chip, 16, 20, 1024, 256, 256**-0.5)


@pytest.mark.parametrize("batch", [64, 32], ids=["ci_w1024.pretrain_padded", "na_w1024.pretrain"])
def test_flash_attention_s256_takes_four_rows_a_step(one_chip, batch):
    """Rows of 256 events, 8 heads of 128: the padded cell's 64 rows a step
    and the NA cell's 32 (its sequence module's global layers)."""
    from eventstreamgpt_tpu.ops.pallas_flash import flash_block_sizes

    assert flash_block_sizes(batch, 256, 8, 128).rows == 4
    _flash_compile(one_chip, batch, 8, 256, 128, 1.0)


def test_flash_attention_head_width_64_rides_in_a_256_lane_group(one_chip):
    """``chip_smoke.py``'s parity model: 4 heads of 64 at S = 256. A head
    narrower than a lane tile is a slice of its group's block."""
    from eventstreamgpt_tpu.ops.pallas_flash import flash_block_sizes

    assert flash_block_sizes(4, 256, 4, 64) == (4, 4, 256, 256)
    _flash_compile(one_chip, 4, 4, 256, 64, 1.0)


def test_flash_kernels_carry_the_scope_and_the_names_the_roofline_reads(one_chip):
    """What a device trace shows of the op: the three Mosaic calls are named
    as `benchmark/metrics/flash_attn_roofline.py::KERNELS` finds them, and
    every operation of the forward and of the backward rule (JAX traces a
    custom_vjp's rules without the caller's name stack) is under
    ``es.attn_global``."""
    import re

    from benchmark.harness.scopes import scope_of
    from benchmark.metrics.flash_attn_roofline import KERNELS

    text = _flash_compile(one_chip, 16, 8, 1024, 128, 1.0)
    calls = dict(re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text))
    assert len(calls) == 3
    for stem in ("flash_attention", "flash_mha_bwd_dkv", "flash_mha_bwd_dq"):
        (name,) = [n for n in calls if n.startswith(stem)]
        assert any(needle in name for needle in KERNELS)
        assert scope_of(calls[name])[0] == "attn_global"
    phases = {scope_of(path)[1] for path in calls.values()}
    assert phases == {"forward", "backward"}
    op_names = [n for n in re.findall(r'op_name="([^"]*)"', text) if "flash" in n or "es." in n]
    assert op_names and all(scope_of(n)[0] == "attn_global" for n in op_names)


def test_held_experts_at_the_cells_shapes(one_chip, monkeypatch):
    """The routed layer's dispatch, grouped products and combine
    (`models/moe.py::held_experts_output`), forward and gradient, at
    `glm47flash_ep8.pretrain_packed`'s shapes: 16,384 rows of 2,048, top-4,
    8 held experts of inner width 1,536, bf16."""
    from eventstreamgpt_tpu.models.moe import held_experts_output

    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas")
    n, k, h, inner, held = 16384, 4, 2048, 1536, 8
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (
        sds((n, h), jnp.bfloat16), sds((n, k), jnp.int32), sds((n, k), jnp.float32),
        sds((held, h, inner), jnp.bfloat16), sds((held, h, inner), jnp.bfloat16), sds((held, inner, h), jnp.bfloat16),
    )

    def grad(rows, chosen, weights, *w):
        loss = lambda rows, weights, *w: held_experts_output(rows, chosen, weights, *w, offset=0)[0].sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(rows, weights, *w)

    text = _compile(grad, *args)
    assert " while(" in text  # the buffer is walked as far as the held pairs reach


# The latent-attention cells' shapes: `glm47flash_ep8.pretrain_packed`, 16 rows of 1,024 events, 20 heads of 192 + 64
# (value 256, a head's rope lanes the second half of its second tile); `xing40_a4b_ep8.pretrain_packed`, 8 rows, 32
# heads of 128 + 64 (value 128, the rope lanes at lane 0 of a tile for the even heads and at lane 64 for the odd)
MLA_SHAPES = {
    "glm47flash_ep8": dict(B=16, S=1024, H=20, nope=192, rope=64),
    "xing40_a4b_ep8": dict(B=8, S=1024, H=32, nope=128, rope=64),
}


@pytest.mark.parametrize("cell", list(MLA_SHAPES))
def test_rope_join_and_its_transpose_at_the_cells_shapes(one_chip, cell):
    """`ops.pallas_rope_join.rope_join` (latent attention's RoPE and
    nope/rope join, in place on ``[16, 1024, 20 * 256]`` and on ``[8, 1024,
    32 * 192]`` bf16) and its transpose compile for the chip, and both Mosaic
    calls carry ``es.attn_latent``: JAX traces a custom_vjp's rules without
    the caller's name stack, so a rule that did not name its scope would leave
    ``scoped_pct.train`` short of the pass's time."""
    import re

    from benchmark.harness.scopes import scope_of
    from eventstreamgpt_tpu.ops.pallas_rope_join import rope_join

    B, S, H, nope, rope = MLA_SHAPES[cell].values()
    wide = jax.ShapeDtypeStruct((B, S, H * (nope + rope)), jnp.bfloat16, sharding=one_chip)
    k_r = jax.ShapeDtypeStruct((B, S, rope), jnp.bfloat16, sharding=one_chip)
    positions = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)

    def grad(q, k, r, p):
        def loss(q, k, r):
            with jax.named_scope("layer"):
                query, key = rope_join(q * 2, k * 2, r, p, heads=H, rope=rope, theta=1e6)
            return (query.astype(jnp.float32) * key.astype(jnp.float32)).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, r)

    text = _compile(grad, wide, wide, k_r, positions)
    calls = dict(re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text))
    assert sorted(n.rstrip(".0123456789") for n in calls) == ["rope_join", "rope_join_transpose"]
    assert {scope_of(path) for path in calls.values()} == {("attn_latent", "forward"), ("attn_latent", "backward")}
    # in place: each call's big outputs are its big operands
    assert text.count("output_to_operand_aliasing") == 2


def _latent_layer(one_chip, monkeypatch, name="glm47flash_ep8", **widths):
    """One `LatentAttention` layer of `benchmark/configs/<name>.json` as the
    chip's backend traces it (the module asks `jax.default_backend`), with
    shapes for its forward + gradient at the cell's rows."""
    import json
    from pathlib import Path

    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
    from eventstreamgpt_tpu.models.latent_attention import LatentAttention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ESGPT_PALLAS_IMPL", raising=False)
    cell = json.loads((Path(__file__).parents[1] / "benchmark" / "configs" / f"{name}.json").read_text())
    module = LatentAttention(StructuredTransformerConfig(**{**cell["config"], **widths}))
    B, S = MLA_SHAPES[name]["B"], MLA_SHAPES[name]["S"]
    x = jax.ShapeDtypeStruct((B, S, module.config.hidden_size), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype), None, jnp.zeros(seg.shape, seg.dtype)))
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)

    def grad(p, x_, seg_):
        return jax.grad(lambda p, x_: module.apply(p, x_, None, seg_).astype(jnp.float32).sum(), argnums=(0, 1))(p, x_)

    return grad, (params, x, seg)


@pytest.mark.parametrize("name, over", [("glm47flash_ep8", 100e6), ("xing40_a4b_ep8", 60e6)])
def test_latent_attention_layer_holds_no_relayout_between_its_products_and_the_flash_kernels(one_chip, monkeypatch, name, over):
    """The cell's shapes engage the assembly, and then nothing of q, k, v or
    their gradients (168 MB each in `glm47flash_ep8` at 16 rows; 101 and 67 MB
    in `xing40_a4b_ep8` at 8) is sliced, concatenated, padded or re-laid by
    XLA between the latent products and the three flash kernels: the entry
    computation holds no ``copy``, ``slice``, ``split``, ``pad_*``,
    ``select_*``, ``broadcast_*`` or ``concatenate`` of that size (with XLA's
    own assembly `glm47flash_ep8`'s layer holds 7 copies and 4 slices, and
    `xing40_a4b_ep8`'s sixteen such arrays over 30 MB, 1.64 GB). One is left
    that is not latent attention's: the flash op's ``di`` (`ops/
    pallas_flash.py::_backward`), whose float32 ``o * do`` XLA re-lays
    events-minor before it reduces it."""
    import re

    import numpy as np

    grad, args = _latent_layer(one_chip, monkeypatch, name)
    with warnings.catch_warnings():  # and the layer says nothing of a fallback
        warnings.simplefilter("error")
        text = _compile(grad, *args)
    assert "rope_join" in text and "rope_join_transpose" in text
    entry = text[text.index("ENTRY") :]
    itemsize = {"bf16": 2, "f32": 4, "s32": 4}
    moved = []
    for op, dtype, dims, op_name in re.findall(
        r'%((?:copy|slice|split|pad_|select_|broadcast_|concatenate)[\w.\-]*) = (\w+)\[([\d,]+)\][^\n]*?op_name="([^"]*)"', entry
    ):
        if np.prod([int(n) for n in dims.split(",")]) * itemsize.get(dtype, 4) > over:
            moved.append((op, f"{dtype}[{dims}]", op_name))
    flash_di = [m for m in moved if "es.attn_global/jit(_backward)/mul" in m[2]]
    assert [m for m in moved if m not in flash_di] == [], moved
    assert len(flash_di) <= 1


def test_latent_attention_warns_once_where_the_widths_are_not_lane_aligned(one_chip, monkeypatch):
    """Four heads of 96 + 64 are five lane tiles, but the first head's rope
    lanes would lie across lanes 96-159, in two tiles: asked for
    ``pallas_flash`` on a TPU, the layer assembles q, k and v with XLA and
    says so, once a trace."""
    grad, args = _latent_layer(one_chip, monkeypatch, qk_nope_head_dim=96, qk_rope_head_dim=64, v_head_dim=128)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        jax.eval_shape(grad, *args)
    assembly = [w for w in caught if "assembling q, k and v with XLA" in str(w.message)]
    assert len(assembly) == 1, [str(w.message) for w in caught]


@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_ssd_scan_kernels_at_the_cells_shapes(one_chip, direction):
    """`ops/pallas_ssd_scan.py` as `nemotron_twotower_ep16.pretrain_packed`
    calls it: 16 rows of 1,024 events, 64 heads of 64 in 8 groups, a state of
    128, chunks of 128, bf16. Forward is one Mosaic call; the gradient holds
    the forward that saves the chunks' entering states and the backward."""
    from eventstreamgpt_tpu.ops.pallas_ssd_scan import ssd_scan_kernels

    B, S, H, P, G, N = 16, 1024, 64, 64, 8, 128
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    x, dt, a = shaped((B, S, H, P), jnp.bfloat16), shaped((B, S, H), jnp.float32), shaped((H,), jnp.float32)
    bmat = cmat = shaped((B, S, G, N), jnp.bfloat16)
    ordinal, skip = shaped((B, S), jnp.int32), shaped((H,), jnp.float32)

    def scan(x, dt, a, bmat, cmat, skip, ordinal):
        return ssd_scan_kernels(x, dt, a, bmat, cmat, ordinal, skip, chunk=128)

    def grad(x, dt, a, bmat, cmat, skip, ordinal):
        loss = lambda *v: scan(*v, ordinal).astype(jnp.float32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))(x, dt, a, bmat, cmat, skip)

    text = _compile(scan if direction == "forward" else grad, x, dt, a, bmat, cmat, skip, ordinal)
    assert text.count('custom_call_target="tpu_custom_call"') == (1 if direction == "forward" else 2)


# `nemotron_twotower_ep16.pretrain_packed`'s rows: 16 of 1,024 events at hidden 2,688
HYBRID_BLOCKS = {
    # letter: (layer of MEMEM*EME, parameters, Mosaic calls, temporaries in GB at most, largest temporary in MB at most)
    "M": (0, 38_744_896, 2, 2.2, 420),
    "E": (1, 100_125_440, 6, 1.4, 420),
    "*": (5, 23_399_040, 3, 1.4, 420),
}
_ITEMSIZE = {"bf16": 2, "f32": 4, "s32": 4, "pred": 1, "s8": 1}


def _hybrid_block_gradient(one_chip, monkeypatch, layer, name="nemotron_twotower_ep16", B=16):
    """Layer ``layer`` of `benchmark/configs/<name>.json` under its ``block``
    remat, the gradient in its parameters and its input on ``[B, 1024,
    hidden]`` bf16 (``hc_mult`` such planes where the configuration carries
    residual streams) compiled as the chip's backend traces it: ``(compiled,
    parameters)``."""
    import json
    from pathlib import Path

    from eventstreamgpt_tpu.models.blocks import KindsBlock
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig
    from eventstreamgpt_tpu.models.transformer import remat_block_cls

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ESGPT_PALLAS_IMPL", raising=False)
    model = json.loads((Path(__file__).parents[1] / "benchmark" / "configs" / f"{name}.json").read_text())
    cfg = StructuredTransformerConfig(**model["config"])
    block = remat_block_cls(cfg, False, KindsBlock)(cfg, layer_id=layer)
    S = 1024
    x = jax.ShapeDtypeStruct((B, S, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)
    if cfg.hc_mult > 1:
        x = (x,) * cfg.hc_mult
    mask = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)
    params = jax.eval_shape(
        lambda: block.init(
            jax.random.PRNGKey(0), jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), x),
            jnp.ones(mask.shape, bool), None, False, False, False,
            jnp.zeros(seg.shape, seg.dtype),
        )
    )
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)

    def grad(p, x_, mask_, seg_):
        def loss(p, x_):
            out, _ = block.apply(p, x_, mask_, None, False, False, False, seg_, mutable=["routing"])[0]
            return sum(o.astype(jnp.float32).sum() for o in jax.tree_util.tree_leaves(out))

        return jax.grad(loss, argnums=(0, 1))(p, x_)

    return jax.jit(grad).lower(params, x, mask, seg).compile(), n_params


def _temporaries(text):
    """``(bytes, dtype, dims, operation)`` of what the compiled entry computation writes."""
    import re

    import numpy as np

    entry = text[text.index("ENTRY") :]
    return [
        (int(np.prod([int(n) for n in dims.split(",")])) * _ITEMSIZE.get(dtype, 4), dtype, dims, op)
        for dtype, dims, op in re.findall(r"%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(", entry)
        if op not in ("parameter", "get-tuple-element", "bitcast")
    ]


@pytest.mark.parametrize("letter", list(HYBRID_BLOCKS))
def test_hybrid_blocks_at_the_cells_shapes(one_chip, monkeypatch, letter):
    """One Mamba-2 (`M`), one routed relu^2 (`E`) and one grouped-query
    attention (`*`) block of `benchmark/configs/nemotron_twotower_ep16.json`
    under its ``block`` remat, forward and gradient on ``[16, 1024, 2688]``
    bf16, as the chip's backend traces them. `M` holds the scan's two kernels
    (`ops/pallas_ssd_scan.py`: the forward that the remat runs again, which
    saves the chunks' entering states, and the backward; the block's first
    forward is dead code in the gradient of a sum) and no decay plane: its
    largest temporary is ``in_proj``'s bf16 output (338 MB), then the gate's
    three float32 broadcasts of a group's rsqrt (268 MB each, the parent's
    too), 2.11 GB of temporaries in all against 2.25 GB with XLA's scan (and
    2.37 GB before the grouped norm took its mean over the plane as it is
    tiled: the kernels' row-major ``y`` was re-laid for it three times). `E` holds megablox' ``gmm`` twice forward, ``gmm`` and ``tgmm`` twice
    backward at 2,688 x 1,856 (the contraction 2,688 takes the 128 tile, the
    width 1,856 no listed tile and so the whole dimension), 1.16 GB; `*` the
    three flash kernels at 32 heads (the two key/value heads repeated before
    them), 1.16 GB."""
    layer, n_params, n_calls, temp_gb, largest_mb = HYBRID_BLOCKS[letter]
    compiled, counted = _hybrid_block_gradient(one_chip, monkeypatch, layer)
    assert counted == n_params
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_calls
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    assert max(size for size, *_ in _temporaries(text)) < largest_mb * 1e6


def test_scan_kernels_carry_the_scope_and_leave_no_decay_plane(one_chip, monkeypatch):
    """What a device trace shows of the `M` block's scan: both Mosaic calls
    are named ``ssd_scan_fwd`` / ``ssd_scan_bwd`` and lie under
    ``es.ssm_scan`` (JAX traces a custom_vjp's rules without the caller's name
    stack, so the rules name the scope), the one in the recomputed forward,
    the other in the backward; nothing under that scope is an XLA product any
    more (the chunked form's four einsums are inside the kernels), and no
    float32 ``Q x Q`` plane over 100 MB is written (``L`` whole is 537 MB)."""
    import re

    from benchmark.harness.scopes import scope_of

    compiled, _ = _hybrid_block_gradient(one_chip, monkeypatch, HYBRID_BLOCKS["M"][0])
    text = compiled.as_text()
    calls = dict(re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text))
    assert sorted(name.split(".")[0] for name in calls) == ["ssd_scan_bwd", "ssd_scan_fwd"]
    assert {scope_of(path) for path in calls.values()} == {("ssm_scan", "recompute"), ("ssm_scan", "backward")}
    under_the_scope = [n for n in re.findall(r'op_name="([^"]*)"', text) if scope_of(n)[0] == "ssm_scan"]
    assert under_the_scope and not [n for n in under_the_scope if "dot_general" in n]
    planes = [t for t in _temporaries(text) if t[1] == "f32" and t[2].endswith(",128,128") and t[0] > 100e6]
    assert planes == []


# `xing40_a4b_ep8.pretrain_packed`'s rows: 8 of 1,024 events, four streams of 3,584
STREAMED_BLOCKS = {
    # kind: (layer, parameters, Mosaic calls, temporaries in GB at most)
    "latent + swiglu": (0, 128_196_918, 5, 1.5),
    "latent + routed": (1, 128_426_358, 17, 1.7),
}


@pytest.mark.parametrize("kind", list(STREAMED_BLOCKS))
def test_streamed_blocks_at_the_cells_shapes(one_chip, monkeypatch, kind):
    """The dense and a routed block of `benchmark/configs/xing40_a4b_ep8.json`
    under its ``block`` remat on four streams ``[8, 1024, 3584]`` bf16, forward
    and gradient, as the chip's backend traces them. The core at a key width
    of 192 (as it is: two heads are three lane tiles) beside a value width of 128 is the three flash
    kernels and nothing of ``[B, H, S, S]`` is written (33.5M elements a head
    plane); the four streams are never one float32 array (470 MB: a first
    version that stacked them held four, and the block compiled to 4.4 GB of
    temporaries where this one holds 1.33 and 1.54); q, k and v reach the
    kernels through `rope_join` in the recomputed forward and its transpose
    in the backward, two Mosaic calls more than the three (the block's first
    forward is dead code in the gradient of a sum), and no ``copy`` of a q, k
    or v plane (101 MB at the key width) is left but the flash op's ``di``;
    the routed block adds megablox' twelve calls."""
    layer, n_params, n_calls, temp_gb = STREAMED_BLOCKS[kind]
    compiled, counted = _hybrid_block_gradient(one_chip, monkeypatch, layer, name="xing40_a4b_ep8", B=8)
    assert counted == n_params
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_calls
    for kernel in ("flash_attention", "flash_mha_bwd_dkv", "flash_mha_bwd_dq", "rope_join", "rope_join_transpose"):
        assert kernel in text
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    written = _temporaries(text)
    assert not [t for t in written if t[2] in ("8,32,1024,1024", "8,1024,32,1024")]
    assert max(size for size, *_ in written) < 160e6
    assert [t[1:3] for t in written if t[3].startswith("copy") and t[0] > 100e6] == [("f32", "1024,8,32,128")]
    assert not [t for t in written if t[1] == "f32" and t[2].endswith("1024,3584") and t[0] > 120e6], "a float32 plane of the streams"


# `lfm2_8b_a1b_ep4.pretrain_packed`'s rows: 16 of 1,024 events at hidden 2,048
CONV_BLOCKS = {
    # kind: (layer, parameters, Mosaic calls, temporaries in GB at most)
    "conv + swiglu": (0, 60_827_648, 0, 1.3),
    "mha + routed": (1, 98_635_936, 12, 1.9),
    "conv + routed": (2, 104_933_408, 9, 1.7),
}


@pytest.mark.parametrize("kind", list(CONV_BLOCKS))
def test_conv_hybrid_blocks_at_the_cells_shapes(one_chip, monkeypatch, kind):
    """The dense, the attention and a convolution's routed block of
    `benchmark/configs/lfm2_8b_a1b_ep4.json` under its ``block`` remat on
    ``[16, 1024, 2048]`` bf16, forward and gradient, as the chip's backend
    traces them (sandbox compile: 1.11, 1.71 and 1.52 GB of temporaries). The
    gated short convolution is XLA's and holds no Mosaic call; its largest
    temporary is ``W_in``'s bf16 output (201 MB). The attention block holds the
    three flash kernels at 32 heads of 64 (the 8 key/value heads repeated
    before them) and megablox' nine calls (three gated products forward, each
    one's ``gmm`` and ``tgmm`` backward); nothing of ``[B, H, S, S]`` is written."""
    layer, n_params, n_calls, temp_gb = CONV_BLOCKS[kind]
    compiled, counted = _hybrid_block_gradient(one_chip, monkeypatch, layer, name="lfm2_8b_a1b_ep4")
    assert counted == n_params
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_calls
    if kind.startswith("mha"):
        for kernel in ("flash_attention", "flash_mha_bwd_dkv", "flash_mha_bwd_dq"):
            assert kernel in text
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9
    written = _temporaries(text)
    assert not [t for t in written if t[2] in ("16,32,1024,1024", "16,1024,32,1024")]
    assert max(size for size, *_ in written) < 240e6


HEAD_SHAPES = {  # rows, events, hidden; event types, labs, medications, static codes (`benchmark/workloads/*.json`)
    "ci_w1024.pretrain_packed": (16, 1024, 1024, 40, 3500, 500, 16),
    "nemotron_twotower_ep16.pretrain_packed": (16, 1024, 2688, 40, 12827, 3500, 16),
}


@pytest.mark.parametrize("cell", list(HEAD_SHAPES))
def test_head_label_planes_reach_the_loss_in_the_layout_it_reads(one_chip, monkeypatch, cell):
    """The CI head's classification loss and gradient at a cell's shape, as
    the chip's backend traces it: both multi-label planes come from
    `ops.pallas_multihot.multihot_any`'s Mosaic call, events on the lanes
    where the unified vocabulary is no multiple of 128 (4,057: XLA lays the
    head's kernel vocabulary-major and the scores events-minor) and the
    vocabulary on them where it is (16,384), so that nothing of a plane's size
    is written but the plane: no ``pred[...]`` plane of the compare-any and no
    ``copy`` over 50 MB under ``es.heads_cls`` (the parent re-laid 210 + 57 MB
    of ``pred`` in the hybrid cell; a vocabulary-minor plane would be re-laid
    in the CI cells)."""
    import re

    import numpy as np

    from eventstreamgpt_tpu.data.types import EventStreamBatch
    from eventstreamgpt_tpu.models.ci_model import ConditionallyIndependentGenerativeOutputLayer
    from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("ESGPT_PALLAS_IMPL", raising=False)
    B, S, hidden, *sizes = HEAD_SHAPES[cell]
    sizes = dict(zip(("event_type", "lab", "med", "demo"), sizes))
    offsets, at = {}, 1
    for name, size in sizes.items():
        offsets[name], at = at, at + size
    config = StructuredTransformerConfig(
        vocab_sizes_by_measurement=sizes,
        vocab_offsets_by_measurement=offsets,
        measurements_idxmap={"event_type": 1, "lab": 2, "med": 3, "demo": 4},
        measurements_per_generative_mode={
            "single_label_classification": ["event_type"],
            "multi_label_classification": ["lab", "med"],
            "multivariate_regression": ["lab"],
        },
        max_seq_len=S, hidden_size=hidden, head_dim=128, num_attention_heads=hidden // 128,
        num_hidden_layers=1, intermediate_size=hidden, precision="bf16",
    )  # fmt: skip
    layer = ConditionallyIndependentGenerativeOutputLayer(config)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    batch = EventStreamBatch(
        event_mask=sds((B, S), jnp.bool_),
        time_delta=sds((B, S), jnp.float32),
        dynamic_indices=sds((B, S, 24), jnp.int32),
        dynamic_measurement_indices=sds((B, S, 24), jnp.int32),
        dynamic_values=sds((B, S, 24), jnp.float32),
        dynamic_values_mask=sds((B, S, 24), jnp.bool_),
        segment_ids=sds((B, S), jnp.int32),
    )
    encoded = sds((B, S, hidden), jnp.bfloat16)
    zeros = lambda tree: jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, x.dtype), tree)  # noqa: E731
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), zeros(batch), zeros(encoded)))
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), params)

    def loss(p, e, b):
        losses, _, _ = layer.apply(p, b, e, set(sizes) - {"demo"}, method=layer.get_classification_outputs)
        return sum(losses.values())

    text = _compile(lambda p, e, b: jax.value_and_grad(loss, argnums=(0, 1))(p, e, b), params, encoded, batch)
    kernel = "_anyhot_2d" if config.vocab_size % 128 == 0 else "_anyhot_events_minor"
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    planes = dict(re.findall(rf"%({kernel}[\w.]*) = s8\[([\d,]+)\]", text))
    written = _temporaries(text)
    assert not [t for t in written if t[1] == "pred" and t[0] > 1e6], "a compare-any plane"
    entry = text[text.index("ENTRY") :]
    copies = re.findall(r'%(copy[\w.\-]*) = (\w+)\[([\d,]+)\][^\n]*?op_name="([^"]*es\.heads_cls[^"]*)"', entry)
    sized = [(op, dtype, dims) for op, dtype, dims, _ in copies if np.prod([int(n) for n in dims.split(",")]) * _ITEMSIZE.get(dtype, 4) > 50e6]
    assert sized == []
    from eventstreamgpt_tpu.ops.pallas_multihot import _any_lane_tiles

    padded = (lambda v: _any_lane_tiles(v)[1]) if kernel == "_anyhot_2d" else (lambda v: -(-v // 256) * 256)
    assert sorted(np.prod([int(n) for n in dims.split(",")]) for dims in planes.values()) == sorted(B * S * padded(sizes[m]) for m in ("lab", "med"))
