"""AOT compiles of every Pallas kernel for a described (not attached)
TPU v5e, at the shapes the main paths use. The chip's compiler is
installed in the CPU sandbox; what it refuses here it refuses on the
chip (a uint32 -> f32 cast in the fp8 kernel passed every
interpret-mode test and was refused by Mosaic). A compile that passes
is not a chip run — numerics and times come from ``chip_smoke.py``.

All in ONE file, topology described inside a fixture: only one process
may load the TPU library, so nothing here touches it at import or
collection time (xdist workers must collect the same tests).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BUCKET_ELEMS = 4096000 // 4     # one BPS_PARTITION_BYTES bucket of f32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip):
    """``compile_for_chip(fn, (shape, dtype), ...)``: compile ``fn`` for
    the described chip and require a Mosaic kernel in the result. A
    described-device executable can be written to the persistent cache
    but not read back, so the cache is off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """The compression kernels pick interpret mode from the attached
    device (CPU here); steer them to the Mosaic path for the compile."""
    from byteps_tpu.ops.compression import pallas_kernels as pk
    pk._interpret.cache_clear()
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    return pk


@pytest.mark.parametrize("shape,causal,extra,lane_dense", [
    # BERT-large, batch 64 x seq 512: heads as 64-lane slices, ht 4 and 2
    ((64, 512, 16, 64), False, None, True),
    ((64, 512, 8, 128), False, None, False),    # its d_head-128 twin
    # GPT-2-small at 32k, causal: the online forward keeps [b, h, s, d]
    ((1, 32768, 12, 64), True, None, False),
    # GPT-2-medium's cell: split backward, nq = 2, ht = 2 (the least
    # tile that fills the lanes)
    ((8, 1024, 16, 64), True, None, True),
    ((256, 128, 16, 64), False, None, True),    # BERT phase 1: ht = 8
    ((8, 1024, 8, 64), False, "rel_table", False),  # T5's score-bias form
], ids=["bert_large", "dh128", "gpt2_32k_causal", "gpt2_medium_causal",
        "bert_s128", "rel_table"])
def test_flash_fwd_bwd_compiles_for_v5e(compile_for_chip, shape, causal,
                                        extra, lane_dense):
    """Mosaic takes every flash call of the main paths, and each in the
    layout its shape chooses: [b, s, heads*d] operands where the heads
    are 64 wide and the forward is one block, [b, h, s, d] elsewhere."""
    from byteps_tpu.ops.flash_attention import flash_attention
    b, s, h, d = shape
    extra_shape = {None: [], "rel_table": [((h, 32), jnp.float32)]}[extra]

    def loss(q, k, v, *e):
        return (flash_attention(q, k, v, causal, **dict(zip([extra], e)))
                .astype(jnp.float32) ** 2).sum()

    text = compile_for_chip(
        jax.value_and_grad(loss, argnums=tuple(range(3 + len(extra_shape)))),
        *[(shape, jnp.bfloat16)] * 3, *extra_shape)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "bps_flash" in line]
    assert calls
    dense, major = f"bf16[{b},{s},{h * d}]", f"bf16[{b},{h},{s},{d}]"
    for line in calls:
        assert (dense in line, major in line) == (lane_dense,
                                                  not lane_dense), line


@pytest.mark.parametrize("seq,heads,kv_heads,window", [
    (8192, 8, 1, 2048), (8192, 8, 1, None), (8192, 32, 2, None),
    (512, 8, 1, 128)],
    ids=["band_8k", "triangle_8k", "triangle_8k_fold16", "one_block_band"])
def test_flash_grouped_window_compiles_for_v5e(compile_for_chip, seq, heads,
                                               kv_heads, window):
    """The grouped-kv decoders' calls at width 128, at the blocks the rule
    gives them (1024 x 1024 at 8k: ``_default_block``), forward and
    backward: Trinity's band layers and its full layer (8 query heads on
    one kv head), Nemotron's triangle (32 on 2: a fold of 16) through the
    online forward and the split backward with the mask on the edge
    blocks alone and the held index maps, and the one-block pair at 512."""
    from byteps_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, window=window)
                .astype(jnp.float32) ** 2).sum()

    text = compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                            ((2, seq, heads, 128), jnp.bfloat16),
                            *[((2, seq, kv_heads, 128), jnp.bfloat16)] * 2)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "bps_flash" in line]
    assert len(calls) == (3 if seq > 1024 else 2)


@pytest.mark.parametrize("seq,kernels", [(8192, 3), (1024, 2)],
                         ids=["triangle_8k", "one_block_pair"])
def test_flash_with_a_value_width_of_its_own_compiles_for_v5e(
        compile_for_chip, seq, kernels):
    """Latent attention's call: 32 heads, q.k over 192 lanes (one and a
    half lane tiles) and values of 128, head-major, the blocks of 1024
    that ``_default_block`` gives such a call (the online forward and the
    split backward at 8k; one forward block and the fused backward at
    1,024 keys)."""
    from byteps_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return (flash_attention(q, k, v, True).astype(jnp.float32) ** 2).sum()

    text = compile_for_chip(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        *[((2, seq, 32, 192), jnp.bfloat16)] * 2,
        ((2, seq, 32, 128), jnp.bfloat16))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "bps_flash" in line]
    assert len(calls) == kernels
    for line in calls:
        assert f"bf16[2,32,{seq},192]" in line and (
            f"bf16[2,32,{seq},128]" in line)


def test_onebit_pack_unpack_compile_for_v5e(compile_for_chip, mosaic):
    n = BUCKET_ELEMS
    chunks = (n + mosaic.PACK - 1) // mosaic.PACK
    compile_for_chip(lambda x: mosaic.onebit_pack(x, chunks),
                     ((n,), jnp.float32))
    compile_for_chip(lambda p: mosaic.onebit_unpack(p, n),
                     ((chunks,), jnp.uint32))


def test_int8_quantize_dequantize_compile_for_v5e(compile_for_chip, mosaic):
    n = BUCKET_ELEMS
    compile_for_chip(mosaic.int8_quantize,
                     ((n,), jnp.float32), ((), jnp.float32))
    compile_for_chip(lambda q, s: mosaic.int8_dequantize(q, s, n),
                     ((n,), jnp.int8), ((), jnp.float32))


@pytest.mark.parametrize("kind", ["E4M3", "E5M2"])
def test_fp8_sr_quantize_compiles_for_v5e(compile_for_chip, mosaic, kind):
    from byteps_tpu.ops.compression import fp8sr
    k = getattr(fp8sr, kind)
    compile_for_chip(
        lambda x, s, seed: mosaic.fp8_sr_quantize(x, s, seed, k),
        ((BUCKET_ELEMS,), jnp.float32), ((), jnp.float32),
        ((), jnp.uint32))


@pytest.mark.parametrize("hidden,width,k,held,tiles", [
    (2048, 1024, 8, 16, 272), (2688, 1856, 6, 8, 200)],
    ids=["trinity_mini", "nemotron3_nano"])
def test_routed_row_movement_compiles_for_v5e(compile_for_chip, hidden, width,
                                              k, held, tiles):
    """``bps_moe_take`` (plain and scaled) and ``bps_moe_combine`` (the
    weighted sum, the plain sum, the products with ``d_out``) at the two
    routed cells' shapes: 16,384 tokens in bf16, a worst-case buffer of
    row tiles of 512. Trinity: hidden 2,048 (a row is one (16, 128) tile),
    8 choices, 16 experts held. Nemotron 3 Nano: hidden 2,688 (21 rows of
    128 lanes, padded to 32 in the take's source), experts of 1,856, 6
    choices, 8 held."""
    from byteps_tpu.ops import routed_rows as rr

    tokens, tile = 16384, 512
    assert rr.resolve("gmm", tokens, hidden, width, held, tile) == "gmm"
    bounds = (tokens // 512 * held,)

    def move(x, y, index, num, scale, dest, w, lo, hi, live, lanes):
        bounds = {"lo": lo, "hi": hi, "live": live, "lanes": lanes}
        rows = rr.take_rows(x, index, num, tile, impl="gmm")
        d_y = rr.take_rows(x, index, num, tile, scale=scale, impl="gmm")
        both = [rr.combine_rows(y, dest, weights, bounds, impl="gmm")
                for weights in (w, None)]
        d_w = rr.combine_rows(y, dest, None, bounds, d_out=x, impl="gmm")
        return rows, d_y, both, d_w

    compile_for_chip(
        move, ((tokens, hidden), jnp.bfloat16),
        ((tiles * tile, hidden), jnp.bfloat16),
        ((tiles * tile,), jnp.int32), ((1,), jnp.int32),
        ((tiles * tile,), jnp.float32), ((tokens, k), jnp.int32),
        ((tokens, k), jnp.float32), (bounds, jnp.int32), (bounds, jnp.int32), ((1,), jnp.int32),
        ((tokens // 512, 2, held * 64), jnp.int32))


@pytest.mark.parametrize("tokens,vocab,hidden,dtype,scale", [
    (16384, 25024, 2048, jnp.bfloat16, 2048 ** 0.5),
    (16384, 16384, 2688, jnp.bfloat16, None),
    (32768, 30522, 1024, jnp.bfloat16, None),
    (8192, 50257, 1024, jnp.bfloat16, None),
    (8192, 50257, 1024, jnp.float32, None)],
    ids=["trinity_mini", "nemotron3_nano", "bert_large", "gpt2_medium",
         "float32"])
def test_the_embeddings_backward_compiles_for_v5e(compile_for_chip, tokens,
                                                  vocab, hidden, dtype,
                                                  scale):
    """The grouped form of ``embed_lookup``'s backward at the cells' four
    shapes (and a float32 cotangent: the product at fp32 contract
    precision): the sort, XLA's gather of the sorted rows and
    ``bps_embed_dw``, whose last block of 256 vocabulary rows hangs over
    a vocabulary that is no multiple of 256, of 128 or of 8."""
    from byteps_tpu.models import transformer

    def grad(ids, ct):
        return transformer.embed_grad(ids, ct, vocab, scale, impl="kernels")

    text = compile_for_chip(grad, ((tokens,), jnp.int32),
                            ((tokens, hidden), dtype))
    assert "bps_embed_dw" in text and "bps_moe_take" not in text
    assert f"f32[{vocab},{hidden}]" in text


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688), (2048, 2048),
                                 (1024, 2048), (2048, 1536), (768, 2048)],
                         ids=["up", "down", "trinity_up", "trinity_down",
                              "kanana_up", "kanana_down"])
def test_grouped_products_off_the_lane_tile_compile_for_v5e(compile_for_chip,
                                                            k, n):
    """``bps_gmm``, ``bps_gmm_dx`` and ``bps_gmm_dw`` at the three routed
    cells' expert weights, Nemotron 3 Nano's first: a width of 1,856 (14.5
    lane tiles) against a hidden size of 2,688 (21), each ONE block a
    width since PR 53 (a block equal to the array's dimension hangs over
    nothing), and a contraction over a width that ends in half a tile.
    The compiler must find room for a whole weight twice: each call asks
    for what ``_blocks`` counted, and Mosaic's own count is under it."""
    from byteps_tpu.ops import grouped_matmul as gm

    held, tile, tiles = 8, 512, 200
    assert gm.supported((tiles * tile, k), (held, k, n), tile)

    def grads(lhs, w, group, num):
        out, pull = jax.vjp(lambda lhs, w: gm.grouped_matmul(
            lhs, w, group, num, None, tile, "gmm"), lhs, w)
        return out, pull(out)

    text = compile_for_chip(
        grads, ((tiles * tile, k), jnp.bfloat16), ((held, k, n), jnp.bfloat16),
        ((tiles,), jnp.int32), ((1,), jnp.int32))
    size = r'\[\{"memory_space":"1","offset":"0","size":"(\d+)"\}\]'
    for kernel in gm.KERNELS:
        (line,) = [ln for ln in text.splitlines()
                   if "tpu_custom_call" in ln
                   and f'/{kernel}/pallas_call"' in ln]
        asked, = re.findall('"scoped_memory_configs":' + size, line)
        used, = re.findall('"used_scoped_memory_configs":' + size, line)
        assert int(asked) == gm._blocks(kernel, k, n, tile, 2)[1]
        assert int(used) <= int(asked) <= gm._VMEM_BUDGET


@pytest.mark.parametrize("act,tiles,m", [("gated_silu", 272, 1024),
                                         ("relu2", 200, 1856)])
def test_the_experts_function_compiles_for_v5e(compile_for_chip, act, tiles,
                                               m):
    """``bps_moe_act_fwd`` and ``bps_moe_act_bwd`` at the two routed
    cells' shapes in bf16: a worst-case buffer of 272 tiles of 512 rows
    with gate and up in a row of 2,048, and 200 tiles at a width of 1,856
    (14.5 lane tiles: the last chunk of lanes is half a tile)."""
    from byteps_tpu.ops import routed_act as ra

    tile, width = 512, m * ra.FORMS[act][1]
    assert ra.supported((tiles * tile, width), tile, act)

    def both(h, da, num):
        a, pull = jax.vjp(lambda h: ra.routed_act(h, num, tile, act, "gmm"),
                          h)
        return a, pull(da)[0]

    text = compile_for_chip(
        both, ((tiles * tile, width), jnp.bfloat16),
        ((tiles * tile, m), jnp.bfloat16), ((1,), jnp.int32))
    for kernel in ("bps_moe_act_fwd", "bps_moe_act_bwd"):
        assert kernel in text


def test_state_space_scan_compiles_for_v5e(compile_for_chip):
    """``bps_ssd_fwd`` (with and without the states it saves) and
    ``bps_ssd_bwd`` at Nemotron 3 Nano's shape: 2 x 8,192 positions, 64
    heads of 64 in 8 groups, a state of 128, chunks of 128, bf16. A lane
    tile holds two heads: a column of the group's steps brought to a
    head's lanes, a head's lanes kept by a select, the state transposed
    in scratch."""
    from byteps_tpu.ops import ssd as S

    bsz, s, heads, p, groups, n = 2, 8192, 64, 64, 8, 128
    assert S.supported((bsz, s, heads, p), (bsz, s, groups, n))

    def both(*args):
        y, pull = jax.vjp(lambda *a: S.ssd_kernels(*a, S.CHUNK, False), *args)
        return S.ssd_kernels(*args, S.CHUNK, False), pull(y)

    text = compile_for_chip(
        both, ((bsz, s, heads, p), jnp.bfloat16),
        ((bsz, s, heads), jnp.float32), ((heads,), jnp.float32),
        ((bsz, s, groups, n), jnp.bfloat16),
        ((bsz, s, groups, n), jnp.bfloat16), ((heads,), jnp.float32))
    for kernel in ("bps_ssd_fwd", "bps_ssd_bwd"):
        assert kernel in text
    # the states before each chunk, float32, leave the forward that saves
    assert f"f32[{bsz},{s // S.CHUNK},{n},{heads * p}]" in text


def test_the_stages_beside_the_scan_compile_for_v5e(compile_for_chip):
    """``bps_ssm_conv_fwd`` / ``_bwd`` over ``xBC`` [2, 8192, 6144] (48
    lane tiles in runs of 4, blocks of 512 positions with their halo
    blocks, strips rotated along the sublanes) and ``bps_ssm_norm_fwd`` /
    ``_bwd`` over [2, 8192, 4096] in 8 groups of 4 lane tiles, bf16, at
    Nemotron 3 Nano's shape; nothing float32 of an activation's size
    leaves a kernel."""
    from byteps_tpu.ops import mamba2_kernels as K

    bsz, s, inner, conv_dim, groups = 2, 8192, 4096, 6144, 8
    assert K.conv_supported((bsz, s, conv_dim), (4, conv_dim))
    assert K.norm_supported((bsz, s, inner), groups)

    def both(x, w, bias, y, z, scale):
        out, pull = jax.vjp(K.conv_silu_kernels, x, w, bias)
        normed, pull_norm = jax.vjp(
            lambda *a: K.gated_norm_kernels(*a, groups, 1e-5), y, z, scale)
        return out, pull(out), normed, pull_norm(normed)

    f32, bf16 = jnp.float32, jnp.bfloat16
    text = compile_for_chip(
        both, ((bsz, s, conv_dim), bf16), ((4, conv_dim), f32),
        ((conv_dim,), f32), ((bsz, s, inner), bf16), ((bsz, s, inner), bf16),
        ((inner,), f32))
    for kernel in ("bps_ssm_conv_fwd", "bps_ssm_conv_bwd", "bps_ssm_norm_fwd",
                   "bps_ssm_norm_bwd"):
        assert kernel in text
    assert not re.search(rf"f32\[{bsz},{s},\d+\]", text)


def _flash_forwards(text):
    """(``bps_flash_fwd`` calls in a compiled step, those of them in a
    checkpoint's recompute) by the ``op_name`` each carries, the name
    stack a trace of the chip reads."""
    paths = [m.group(1) for line in text.splitlines()
             if "tpu_custom_call" in line
             for m in [re.search(r'op_name="([^"]*bps_flash_fwd[^"]*)"',
                                 line)] if m]
    return len(paths), sum("rematted_computation" in p for p in paths)


@pytest.mark.parametrize("family,policy,forwards,again", [
    ("transformer", "save_attn", 1, 0), ("transformer", None, 2, 1),
    ("decoder", "save_attn", 3, 0)],
    ids=["transformer", "transformer_minimum_memory", "decoder"])
def test_the_checkpoint_keeps_the_flash_output_on_v5e(
        compile_for_chip, monkeypatch, family, policy, forwards, again):
    """A gradient step's compiled text for the chip (ISSUE 36). Under the
    default the flash forward is there once an attending layer (once in
    the body of the transformer's scan) and never under
    ``rematted_computation``; ``remat_policy=None`` still runs it again
    in the recompute; the decoder's routed layers choose and sort once."""
    from byteps_tpu.models import decoder, gpt2, transformer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if family == "transformer":
        cfg = transformer.TransformerConfig(
            vocab_size=512, hidden=256, layers=2, heads=4, mlp_dim=512,
            max_seq=128, causal=True, remat_policy=policy)
        params = jax.eval_shape(
            lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
        loss = gpt2.causal_lm_loss
    else:
        cfg = decoder.afmoe_config(
            vocab_size=512, hidden=256, heads=2, kv_heads=1, head_dim=128,
            mlp_dim=512, moe_dim=128, window=128, top_k=2, router_outputs=8,
            held=(0, 1, 2, 3), balanced=True, routed_kw={"row_tile": 128},
            layer_kinds=("dense_sliding", "moe_full", "moe_sliding"))
        params = jax.eval_shape(
            lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
        loss = decoder.causal_lm_loss
    assert cfg.remat
    leaves, tree = jax.tree_util.tree_flatten(params)

    def grads(tokens, *leaves):
        return jax.grad(lambda p: loss(p, cfg, tokens))(
            jax.tree_util.tree_unflatten(tree, leaves))

    text = compile_for_chip(grads, ((2, 256 if family == "decoder" else 128),
                                    jnp.int32),
                            *((x.shape, x.dtype) for x in leaves))
    assert _flash_forwards(text) == (forwards, again)
    if family == "decoder":
        sorts = [line for line in text.splitlines()
                 if " sort(" in line and "bps.moe.route" in line]
        # two a routed layer: the choice's top-k and the plan's sort
        assert len(sorts) == 4, sorts
        assert not any("rematted_computation" in s for s in sorts)
        # ... and (ISSUE 52) combine three times a routed layer, never in
        # the recompute: the checkpoint keeps what afmoe's norm after the
        # feed-forward reads
        combines = [line for line in text.splitlines()
                    if "custom-call" in line and re.search(
                        r'op_name="[^"]*bps_moe_combine/pallas_call"', line)]
        assert len(combines) == 6, combines
        assert not any("rematted_computation" in c for c in combines)


def test_flash_at_heads_of_256_compiles_for_v5e(compile_for_chip):
    """Qwen3-Next's full-attention call: 16 query heads of 256 lanes (two
    lane tiles a row) over 2 kv heads, 2 x 8,192, causal, at the blocks of
    1024 x 1024 the rule gives it: the online forward and the split
    backward."""
    from byteps_tpu.ops.flash_attention import flash_attention, supported

    shape, kv = (2, 8192, 16, 256), (2, 8192, 2, 256)
    assert supported(shape, kv, kv)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True).astype(jnp.float32) ** 2).sum()

    text = compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                            (shape, jnp.bfloat16), *[(kv, jnp.bfloat16)] * 2)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "bps_flash" in line]
    assert len(calls) == 3
    for kernel in ("bps_flash_fwd", "bps_flash_bwd_dq", "bps_flash_bwd_dkv"):
        assert kernel in text
    # k and v cross HBM once a kv head, head-major
    assert all("bf16[2,2,8192,256]" in line for line in calls)


def test_the_stages_beside_the_delta_rule_compile_for_v5e(compile_for_chip):
    """``bps_ssm_conv_fwd`` / ``_bwd`` over q, k and v side by side
    [2, 8192, 8192] (64 lane tiles in runs of 4) with a row of zeros for
    the bias the convolution has not, and ``bps_ssm_norm_fwd`` / ``_bwd``
    with the gate AFTER the norm over [2, 8192, 4096] in 32 heads of one
    lane tile, bf16, at Qwen3-Next's shape; nothing float32 of an
    activation's size leaves a kernel."""
    from byteps_tpu.ops import mamba2_kernels as K

    bsz, s, value_dim, conv_dim, heads = 2, 8192, 4096, 8192, 32
    assert K.conv_supported((bsz, s, conv_dim), (4, conv_dim))
    assert K.norm_supported((bsz, s, value_dim), heads)

    def both(x, w, y, z, scale):
        out, pull = jax.vjp(
            lambda x, w: K.conv_silu_kernels(
                x, w, jnp.zeros((conv_dim,), jnp.float32)), x, w)
        normed, pull_norm = jax.vjp(
            lambda *a: K.gated_norm_kernels(*a, heads, 1e-6, 0, K.NORM_STRIP,
                                            False, False), y, z, scale)
        return out, pull(out), normed, pull_norm(normed)

    f32, bf16 = jnp.float32, jnp.bfloat16
    text = compile_for_chip(
        both, ((bsz, s, conv_dim), bf16), ((4, conv_dim), f32),
        ((bsz, s, value_dim), bf16), ((bsz, s, value_dim), bf16),
        ((value_dim,), f32))
    for kernel in ("bps_ssm_conv_fwd", "bps_ssm_conv_bwd", "bps_ssm_norm_fwd",
                   "bps_ssm_norm_bwd"):
        assert kernel in text
    assert not re.search(rf"f32\[{bsz},{s},\d+\]", text)


def test_the_delta_rules_kernels_compile_for_v5e(compile_for_chip):
    """``bps_gdn_inverse`` / ``_bwd`` and ``bps_gdn_fwd`` / ``_bwd`` (a grid
    step a key head: both of its value heads' [128, 128] matrices made in
    VMEM) at Qwen3-Next's shape: 2 x 8,192 positions, 32 value heads of 128
    over 16 key heads, chunks of 128, bf16. The state before each chunk,
    float32, leaves the forward that a backward follows; ``T`` and its
    cotangent cross HBM in bf16; and NO value that XLA makes (a fusion's, a
    convolution's, a broadcast's or a copy's result) has two chunk axes:
    ``A``, the decays, ``k k^T``, ``q k^T``, ``U``, ``W`` and their
    cotangents exist in VMEM alone."""
    from byteps_tpu.ops import gated_delta as G

    bsz, s, hk, hv, d, chunk = 2, 8192, 16, 32, 128, 128
    assert G.supported((bsz, s, hk, d), (bsz, s, hv, d), chunk)

    def both(q, k, v, g, beta):
        out, pull = jax.vjp(
            lambda *a: G.gated_delta_kernels(*a, chunk), q, k, v, g, beta)
        return out, pull(out)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = compile_for_chip(
        both, *[((bsz, s, hk, d), bf16)] * 2, ((bsz, s, hv, d), bf16),
        *[((bsz, s, hv), f32)] * 2)
    for kernel in ("bps_gdn_fwd", "bps_gdn_bwd", "bps_gdn_inverse",
                   "bps_gdn_inverse_bwd"):
        assert len(re.findall(rf"custom-call\(.*/{kernel}/pallas_call",
                              text)) == 1, kernel
    n = s // chunk
    two_chunk_axes = re.compile(
        rf"(f32|bf16)\[{bsz},{n},({hv}|{hk}|{hk},{hv // hk}),{chunk},"
        rf"{chunk}\]")
    made = {}
    for line in text.splitlines():
        found = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) ([\w-]+)\(", line)
        if found and two_chunk_axes.match(found.group(1)):
            made.setdefault(found.group(2), set()).add(
                found.group(1).split("{")[0])
    # the kernels' own: T (bf16) from the inverse, the states (float32) as
    # one of the forward's pair, dT (bf16) as one of the backward's five
    assert made == {
        "custom-call": {f"bf16[{bsz},{n},{hv},{chunk},{chunk}]"},
        "get-tuple-element": {f"f32[{bsz},{n},{hv},{d},{d}]",
                              f"bf16[{bsz},{n},{hv},{chunk},{chunk}]"}}
