"""The LM cell's kernels compile for a described v5e at the published
widths: no chip, no result, only what the chip's compiler would refuse
(a tiling, the fast memory a kernel may use, a batched grouped matmul).
All topology work happens inside the fixtures, in this one file."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.mark.parametrize("block", [512, 1024])
def test_latent_attention_kernels_compile_at_published_widths(one_chip,
                                                              no_cache, block):
    """32 heads, 4,096 positions, 192-wide queries and keys (padded to 256),
    128-wide values, bf16: the forward kernel and the one backward kernel
    that dq, dk and dv all leave."""
    from fedml_tpu.ops.attention import attention

    def step(q, k, v, c):
        return jax.grad(lambda q, k, v: jnp.sum(attention(
            q, k, v, impl="pallas", block_q=block, block_k=block
        ).astype(jnp.float32) * c), argnums=(0, 1, 2))(q, k, v)

    def sd(d):
        return jax.ShapeDtypeStruct((2, 32, 4096, d), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(sd(192), sd(192), sd(128), sd(128)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    # no [T, T] score tensor: the program's temporaries stay under 1 GB
    # (one head's float32 scores alone would be 67 MB, all 64 4.3 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


@pytest.mark.parametrize("heads,window,block", [
    (64, 512, 1024), (64, 512, 512), (48, None, 1024)])
def test_grouped_and_windowed_kernels_compile_at_published_widths(
        one_chip, no_cache, heads, window, block):
    """64 query heads over 8 key-value heads under a window of 512, and 48
    over 8 without one, 4,096 positions, heads of 128, bf16, two sequences:
    forward and the one backward kernel, which sums dk / dv over its group
    and keeps the group's dq in VMEM (16 MiB at 8 heads). Under the
    window the temporaries hold no score tensor either, and the grid sweeps
    2 key tiles a query tile where the causal kernels sweep 4."""
    import importlib

    # the package exports the function under the module's name
    att = importlib.import_module("fedml_tpu.ops.attention")

    def step(q, k, v, c):
        return jax.grad(lambda q, k, v: jnp.sum(att.attention(
            q, k, v, impl="pallas", block_q=block, block_k=block,
            window=window).astype(jnp.float32) * c), argnums=(0, 1, 2))(q, k, v)

    def sd(h):
        return jax.ShapeDtypeStruct((2, h, 4096, 128), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(sd(heads), sd(8), sd(8), sd(heads)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    _dq, dk, dv = jax.eval_shape(step, sd(heads), sd(8), sd(8), sd(heads))
    assert dk.shape == dv.shape == (2, 8, 4096, 128)
    if window:
        tiling = att._tiling(True, 4096, 4096, block, block, window)
        n = 4096 // block
        assert att._band_sweep(tiling, n, n) == 2
        assert att._band_sweep(tiling, n, n, over_queries=True) == 2


@pytest.mark.parametrize("t,kernels", [(8192, 2), (16384, 3)])
def test_backward_form_compiles_on_both_sides_of_the_vmem_budget(
        one_chip, no_cache, t, kernels):
    """8 query heads of 128 over one key-value head under a window of 512,
    one sequence: at 8,192 positions the one backward call keeps 32 MiB of
    dq and asks for 96 MiB of VMEM, the most the shape function grants; at
    16,384 dq does not fit and dk/dv and dq are two kernels."""
    import importlib

    att = importlib.import_module("fedml_tpu.ops.attention")
    assert (att._bwd_vmem(t, 128, 128, 8) is None) == (kernels == 3)

    def step(q, k, v, c):
        return jax.grad(lambda q, k, v: jnp.sum(att.attention(
            q, k, v, impl="pallas", block_q=1024, block_k=1024,
            window=512).astype(jnp.float32) * c), argnums=(0, 1, 2))(q, k, v)

    def sd(h):
        return jax.ShapeDtypeStruct((1, h, t, 128), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(sd(8), sd(1), sd(1), sd(8)).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels


@pytest.fixture()
def grouped_kernels(monkeypatch):
    """``ops/grouped_matmul.py`` asks ``jax.default_backend()`` whether to
    take its kernels, and that is the CPU's here: the chip's choice, with
    the kernels compiled and not interpreted."""
    import fedml_tpu.ops.grouped_matmul as gm
    monkeypatch.setattr(gm, "_pick_impl", lambda impl: "pallas")
    monkeypatch.setattr(gm, "interpret", lambda: False)
    return gm


#: the five sparse cells' ``K x N``, held experts, first and last capacity
GROUPED_SHAPES = {
    "kanana2": (2048, 768, 16, 6144, 49152),
    "ling3": (2560, 768, 8, 4096, 32768),
    "laguna": (2048, 512, 32, 8192, 65536),
    "zaya1": (2048, 2048, 8, 1024, 8192),
    "nemotron3s_up": (1024, 2688, 8, 11264, 90112),
    "nemotron3s_down": (2688, 1024, 8, 11264, 90112),
}


@pytest.mark.parametrize("last", [False, True])
@pytest.mark.parametrize("cell", sorted(GROUPED_SHAPES))
def test_grouped_matmul_compiles_unbatched_at_published_widths(
        one_chip, no_cache, grouped_kernels, cell, last):
    """bf16 rows against the held experts' float32 matrices at a cell's
    first and last row capacity: forward and both gradients are the three
    kernels of the repo's own (none of the compiler's grouped kernels, no
    cast of a matrix outside them), ``d_w`` leaves in float32, and the cost
    they tell XLA is the row slots' (the static capacity), three passes."""
    k, n, held, *capacities = GROUPED_SHAPES[cell]
    rows = capacities[last]
    assert grouped_kernels._tiles(rows, k, n, held, 2, 4)

    def step(x, w, sizes):
        return jax.grad(lambda x, w: jnp.sum(grouped_kernels.grouped_matmul(
            x, w, sizes).astype(jnp.float32) ** 2), argnums=(0, 1))(x, w)

    operands = (
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((held, k, n), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(step).lower(*operands).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 and "ragged-dot" not in text
    assert not re.search(rf"(bf16|f32)\[{held},{k},{n}\][^ ]* convert\(", text)
    dx, dw = jax.eval_shape(step, *operands)
    assert (dx.dtype, dw.dtype) == (jnp.bfloat16, jnp.float32)
    flops = compiled.cost_analysis()["flops"]
    assert flops == pytest.approx(3 * 2 * rows * k * n, rel=0.02)


@pytest.mark.parametrize("capacity", [4096, 8192])
def test_wide_experts_rung_holds_no_copy_of_a_matrix(one_chip, no_cache,
                                                     grouped_kernels,
                                                     capacity):
    """``zaya1_8b``'s held experts (8 of 2048 x 2048, one choice of 8,192
    tokens): the compiled ``_rung`` and ``_rung_vjp`` of a capacity hold no
    float32 -> bf16 ``convert`` of an ``[8, 2048, 2048]`` operand and no
    bf16 -> float32 one (a weight gradient on its way back), and the
    gradients of the three matrices leave as float32."""
    from fedml_tpu.models import moe

    n, d, held = 8192, 2048, 8

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    operands = (sd((n, d), jnp.bfloat16), sd((n,), jnp.int32),
                sd((n,), jnp.int32), sd((held,), jnp.int32),
                sd((1, n), jnp.bool_), sd((n, 1), jnp.float32),
                *[sd((held, d, d), jnp.float32)] * 3)
    ct = (sd((n, d), jnp.float32), ())
    for fn, args in ((moe._rung(capacity), operands),
                     (moe._rung_vjp(capacity), (operands, ct))):
        text = fn.lower(*args).compile().as_text()
        assert "ragged-dot" not in text
        assert not re.search(r"(bf16|f32)\[8,2048,2048\][^ ]* convert\(", text)
        assert text.count("tpu_custom_call") == (3 if fn is moe._rung(
            capacity) else 9)
    grads = jax.eval_shape(moe._rung_vjp(capacity), operands, ct)
    assert [g.dtype for g in grads[2:]] == [jnp.float32] * 3


def test_row_capacities_compile_as_one_conditional_a_pass(one_chip, no_cache,
                                                          grouped_kernels):
    """The held experts' part of a sparse layer at the cell's shapes
    (``models/moe.routed_rows``): forward and backward each compile to one
    conditional with a branch a row capacity, and the backward's
    temporaries stay under the full capacity's rows, ``g``, ``u`` and
    their cotangents (no union of the branches' residuals)."""
    from fedml_tpu.models import moe

    n, k, d, f, held = 8192, 6, 2048, 768, 16
    rungs = moe.row_rungs(n * k)
    assert rungs == (6144, 12288, 24576, 49152)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    operands = (sd((n, d), jnp.bfloat16), sd((n * k,), jnp.int32),
                sd((n * k,), jnp.int32), sd((held,), jnp.int32),
                sd((k, n), jnp.bool_), sd((n, k), jnp.float32),
                sd((held, d, f), jnp.float32), sd((held, d, f), jnp.float32),
                sd((held, f, d), jnp.float32))

    def step(*ops):
        return jax.grad(lambda xf, weights, *w: jnp.sum(moe.routed_rows(
            rungs, "swiglu", xf, *ops[1:5], weights, *w)[0] ** 2), argnums=(0, 1, 2, 3, 4))(
                ops[0], *ops[5:])

    compiled = jax.jit(step).lower(*operands).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 2
    assert text.count("branch_computations={") == 2
    for c in rungs:
        assert f"moe_rows_{c}/" in text
    # 985 MB here against 808 MB with the full capacity as the only rung
    # (rows and y 201 MB each in bf16, g, u and their product 75 MB each,
    # and their cotangents): the bf16 copies of the weights (150 MB) live
    # as long as the conditional they are operands of. The union of four
    # rungs' residuals would add another 0.4 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1e9


@pytest.mark.parametrize("chunk,impl", [(32, "xla"), (64, "xla"),
                                        (32, "pallas"), (64, "pallas")])
def test_delta_rule_scan_compiles_at_published_widths(one_chip, no_cache,
                                                      monkeypatch, chunk, impl):
    """32 heads of 128, 4,096 positions, one sequence, bf16 operands:
    forward and backward of the chunked scan (``ops/kda.py``), as the
    ``jax.numpy`` scan and as the kernel pair (compiled, not interpreted:
    the host here is a CPU). The temporaries stay bounded: the intra-chunk
    part is recomputed, the scan keeps a state every few chunks (2 MB each
    over 32 heads), and no ``[T, T]`` product or per-position state (8.6 GB)
    is ever formed. The kernel pair holds a fifth of the scan's: between its
    two calls only the inputs and the kept states (134 MB) live; the scan's
    stacked operands (176 MB), their cotangents and the intra-chunk part's
    residuals are never in HBM (201 MB compiled against 1,028)."""
    from fedml_tpu.ops import kda

    monkeypatch.setattr(kda, "interpret", lambda: False)

    def step(q, k, v, g, beta, c):
        return jax.grad(lambda *a: jnp.sum(kda.kda_chunked(
            *a, chunk=chunk, impl=impl) * c), argnums=(0, 1, 2, 3, 4))(
                q, k, v, g, beta)

    def sd(dtype, *tail):
        return jax.ShapeDtypeStruct((1, 32, 4096) + tail, dtype,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(
        sd(jnp.bfloat16, 128), sd(jnp.bfloat16, 128), sd(jnp.bfloat16, 128),
        sd(jnp.float32, 128), sd(jnp.float32), sd(jnp.float32, 128)).compile()
    text = compiled.as_text()
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    if impl == "pallas":
        # the forward kernel and the backward kernel, and no loop of XLA's
        assert text.count("tpu_custom_call") == 2 and " while(" not in text
        assert temporaries < 0.4e9
    else:
        assert " while(" in text                  # the scan over the chunks
        assert temporaries < 1.5e9


@pytest.mark.parametrize("name,named", [
    ("kanana2_tiny", dict(mixers=("latent",) * 3, n_group=1, topk_group=1,
                          qk_norm=False, out_gate=False)),
    ("kanana2_tiny", dict(kv_heads=0, window_heads=0, window=0,
                          window_rope_theta=1e4, yarn_factor=0.0,
                          yarn_original=0, yarn_attention_factor=1.0,
                          score="sigmoid")),
    ("ling3_tiny", dict(kv_heads=0, window_heads=0, window=0, yarn_factor=0.0,
                        score="sigmoid")),
    ("kanana2_tiny", dict(ssd_heads=0, ssd_head_dim=64, ssd_state=128,
                          ssd_conv=4, ssd_chunk=256, embed_scale=1.0,
                          residual_scale=1.0, attn_scale=None, logit_scale=1.0,
                          tied_head=False)),
    ("ling3_tiny", dict(ssd_heads=0, embed_scale=1.0, residual_scale=1.0,
                        attn_scale=None, logit_scale=1.0, tied_head=False)),
    ("laguna_tiny", dict(ssd_heads=0, embed_scale=1.0, residual_scale=1.0,
                         attn_scale=None, logit_scale=1.0, tied_head=False)),
    ("kanana2_tiny", dict(cca_conv=(2, 2), router_hidden=0, balance_rate=0.0,
                          scaled_residual=False)),
    ("ling3_tiny", dict(cca_conv=(2, 2), router_hidden=0, balance_rate=0.0,
                        scaled_residual=False)),
    ("laguna_tiny", dict(cca_conv=(2, 2), router_hidden=0, balance_rate=0.0,
                         scaled_residual=False)),
    ("granite4h_tiny", dict(cca_conv=(2, 2), router_hidden=0,
                            balance_rate=0.0, scaled_residual=False))])
def test_older_lm_lowers_the_same_with_the_new_options_at_their_defaults(
        name, named):
    """The options the hybrid decoder added (the mixers' pattern, the
    group-limited router, the query / key norms, the output gate) and those
    the window / full decoder added (the grouped-query mixers' sizes, the
    window, YaRN, the router's score function), the state-space decoder
    (its mixers' sizes, the four multipliers, the tied head) and the
    compressed-attention decoder (the convolutions' positions, the MLP
    router, the choice that is no expert, the scaled residuals) leave the
    older LMs' programs as they were: built with every one of them named at its default
    a model lowers to the same text as built without, and its variable tree
    has no new leaf (CPU fixtures; against the parent commit's text the
    fixtures' round programs were checked by sha256, ``PERF.md`` PR 30,
    PR 32 and PR 37)."""
    from fedml_tpu.core.tasks import nwp
    from fedml_tpu.models import create_model

    def lowered(**kw):
        b = create_model(name, 64, input_shape=(
            32 if name in ("laguna_tiny", "granite4h_tiny") else 16,), **kw)
        v = b.init(jax.random.key(0))

        def step(v, x, y, m):
            def loss(p):
                logits, new = b.apply_train({**v, "params": p}, x, None)
                return nwp.loss(logits, y, m), new
            return jax.value_and_grad(loss, has_aux=True)(v["params"])

        x = jnp.zeros((2,) + tuple(b.input_shape), jnp.int32)
        return (jax.jit(step).lower(v, x, x, jnp.ones((2,))).as_text(),
                jax.tree.map(jnp.shape, v))

    plain, tree = lowered()
    text, tree2 = lowered(**named)
    assert plain == text and tree == tree2
    if name == "kanana2_tiny":
        assert "group_tokens" not in str(tree) and "out_gate" not in str(tree)


@pytest.mark.parametrize("chunk", [128, 256])
def test_state_space_recurrence_compiles_at_published_widths(one_chip, no_cache,
                                                             chunk):
    """64 heads of 64 over a state of 128, 4,096 positions, one sequence,
    bf16 operands: forward and backward of the chunked recurrence
    (``ops/ssd.py``, plain ``jax.numpy``). One loop, the scan over the
    chunks' states; no per-position state (8.6 GB) is ever formed, and the
    masked decays of every head and chunk (``T x Q x H`` float32, 268 MB at
    256) with their cotangents stay under 1.5 GB of temporaries."""
    from fedml_tpu.ops import ssd

    def step(x, dt, a_log, b, c, d, ct):
        return jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(
            *a, chunk=chunk) * ct), argnums=(0, 1, 2, 3, 4, 5))(
                x, dt, a_log, b, c, d)

    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        sd(jnp.bfloat16, 1, 4096, 64, 64), sd(jnp.float32, 1, 4096, 64),
        sd(jnp.float32, 64), sd(jnp.bfloat16, 1, 4096, 128),
        sd(jnp.bfloat16, 1, 4096, 128), sd(jnp.float32, 64),
        sd(jnp.float32, 1, 4096, 64, 64)).compile()
    text = compiled.as_text()
    assert " while(" in text and "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_state_space_mixer_issues_a_product_a_consumer(one_chip, no_cache):
    """The whole mixer at the published widths (hidden 2,048; 64 heads of 64
    over a state of 128: a joint projection of 8,512 columns, 66.5 lane
    tiles), forward and backward in bf16 on one sequence of 4,096: no
    array of all 8,512 columns but the ONE kernel and its gradient, so no
    consumer's slice is cut from a joint output that the compiler would
    have to keep, or compute again, whole (``PERF.md``, PR 38)."""
    from fedml_tpu.models.transformer import Mamba2Mixer

    mixer = Mamba2Mixer(64, 64, 128, dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    v = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), u))

    def step(v, u, c):
        return jax.grad(lambda p, u: jnp.sum(mixer.apply(
            {**v, "params": p}, u).astype(jnp.float32) * c), argnums=(0, 1))(
                v["params"], u)

    text = jax.jit(step).lower(v, u, u).compile().as_text()
    wide = set(re.findall(r" = \(?(\w+\[[\d,]*8512\])", text))
    assert wide and all(shape.endswith("[2048,8512]") for shape in wide), wide
    gp, gu = jax.eval_shape(step, v, u, u)
    assert gp["in_proj"]["kernel"].shape == (2048, 8512)
    assert gu.shape == (1, 4096, 2048)


def test_position_free_attention_compiles_at_heads_of_64(one_chip, no_cache):
    """32 query heads over 8 key-value heads of 64 channels (half a lane
    tile; ``_pad_qk`` leaves them as they are), 4,096 positions, one
    sequence, scores times 1/64: the forward kernel and the one backward
    kernel."""
    import importlib

    att = importlib.import_module("fedml_tpu.ops.attention")

    def step(q, k, v, c):
        return jax.grad(lambda q, k, v: jnp.sum(att.attention(
            q, k, v, impl="pallas", block_q=1024, block_k=1024,
            sm_scale=0.015625).astype(jnp.float32) * c), argnums=(0, 1, 2))(
                q, k, v)

    def sd(h):
        return jax.ShapeDtypeStruct((1, h, 4096, 64), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(sd(32), sd(8), sd(8), sd(32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    _dq, dk, dv = jax.eval_shape(step, sd(32), sd(8), sd(8), sd(32))
    assert dk.shape == dv.shape == (1, 8, 4096, 64)


def test_compressed_conv_attention_compiles_at_published_widths(one_chip,
                                                                no_cache):
    """The whole CCA mixer at the published widths (hidden 2,048; 8 query
    heads over 2 key-value heads of 128: a latent of 1,024, ten heads
    through both convolutions), forward and backward in bf16 on two
    sequences of 4,096: the two attention kernel calls (the forward and the
    ONE backward) and nothing else of Mosaic's; the head-wise convolution's
    kernel gradient comes out as the ``[2, 10, 128, 128]`` leaf; the mixing's
    float32 passes over ``[2, 4096, 1280]`` stay under 1.5 GB of
    temporaries."""
    import importlib

    from fedml_tpu.models.transformer import CompressedConvAttention

    att = importlib.import_module("fedml_tpu.ops.attention")
    mixer = CompressedConvAttention(8, 2, 128, 64, 5e6, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    v = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), x))

    def step(v, x, c):
        return jax.grad(lambda p, x: jnp.sum(mixer.apply(
            {"params": p}, x).astype(jnp.float32) * c), argnums=(0, 1))(
                v["params"], x)

    pick, att._pick_impl = att._pick_impl, lambda impl: "pallas"
    try:
        compiled = jax.jit(step).lower(v, x, x).compile()
    finally:
        att._pick_impl = pick
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    gp, gx = jax.eval_shape(step, v, x, x)
    assert gp["conv1_kernel"].shape == (2, 10, 128, 128)
    assert gp["q_proj"]["kernel"].shape == (2048, 1024)
    assert gp["k_temp"].shape == (2,) and gx.shape == (2, 4096, 2048)


def test_mlp_routed_layer_compiles_at_published_widths(one_chip, no_cache):
    """The sparse sub-layer at the published widths on 8,192 tokens: a
    router of 256 hidden channels with its carry, 17 outputs and ONE choice
    a token, 8 held experts of ``[2048, 2048]`` in bf16: one conditional a
    pass over the four row capacities of 8,192 pairs, the compiler's grouped
    kernels unbatched inside its branches, the carry's cotangent back to the
    layer before, and the load's pull where the balancing bias's gradient
    would be."""
    from fedml_tpu.models import moe

    layer = moe.SharedRoutedMoe(16, 1, 2048, 0, 1.0, 0, 8, jnp.bfloat16,
                                router_hidden=256, eps=1e-5,
                                balance_rate=130.0)

    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, carry = sd(jnp.bfloat16, 2, 4096, 2048), sd(jnp.float32, 8192, 256)
    v = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda k, x, c: layer.init(k, x, False, c),
                       jax.random.key(0), x, carry))
    assert v["params"]["router"]["out_kernel"].shape == (256, 17)
    assert v["params"]["gate"].shape == (8, 2048, 2048)

    def step(v, x, carry, c):
        def loss(p, x, carry):
            out, s = layer.apply({**v, "params": p}, x, False, carry)
            return jnp.sum((out.astype(jnp.float32) * c) ** 2) + jnp.sum(s)
        return jax.grad(loss, argnums=(0, 1, 2))(v["params"], x, carry)

    compiled = jax.jit(step).lower(v, x, carry, x).compile()
    text = compiled.as_text()
    assert moe.row_rungs(8192) == (1024, 2048, 4096, 8192)
    assert text.count(" conditional(") == 2
    for c in moe.row_rungs(8192):
        assert f"moe_rows_{c}/" in text
    gp, gx, gc = jax.eval_shape(step, v, x, carry, x)
    assert gp["router"]["gamma"].shape == () and gc.shape == (8192, 256)
    assert gp["router"]["bias"].shape == (17,)
    assert gx.shape == (2, 4096, 2048)


# -- the one-lane round's branches (parallel/packed._on_flag, PR 42) -----------

#: float32 shapes the LM cells' variable trees hold (kernels, gates, routers,
#: stacked experts, the state-space convolution) and shapes on both sides of
#: each edge of the padding rule
KEPT_SHAPES = [
    (2048, 8512), (8512, 2048), (2560, 32), (2048, 576), (2048, 16032),
    (2560, 19648), (2048, 48), (2048, 64), (256, 17), (2048, 17),
    (12544, 2048), (32784, 2048), (2048, 2048), (4, 4352), (2, 1280),
    (10, 128), (8, 2048, 2048), (16, 2048, 768), (32, 512, 2048),
    (2, 10, 128, 128), (2048,), (), (128, 4), (120, 4), (1024, 1),
    (100, 200), (136, 200), (256, 200), (128, 129), (512, 127), (8, 200),
    (64, 200), (3, 3, 16, 32), (7, 2048, 64), (2, 2048, 8512)]


def test_kept_transposed_is_the_compilers_own_layout(one_chip, no_cache):
    """``_kept_transposed`` says which float32 shapes the TPU keeps with
    their two minor axes swapped; the compiler's own layouts for a program's
    arguments say the same of every shape here. If a compiler changes its
    mind, this fails, and ``_on_flag`` tells its branches the wrong way."""
    from fedml_tpu.parallel.packed import _kept_transposed

    compiled = jax.jit(lambda *xs: [x + 1 for x in xs]).lower(*(
        jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
        for s in KEPT_SHAPES)).compile()
    for shape, fmt in zip(KEPT_SHAPES, compiled.input_formats[0]):
        order = tuple(fmt.layout.major_to_minor)
        plain = tuple(range(len(shape)))
        swapped = plain[:-2] + plain[-2:][::-1]
        assert order == (swapped if _kept_transposed(shape) else plain), shape
    assert sum(map(_kept_transposed, KEPT_SHAPES)) == 17


@pytest.mark.parametrize("form", ["select", "on_flag", "plain_cond"])
def test_flagged_passes_leave_the_loop_carry_as_the_device_keeps_it(
        one_chip, no_cache, form):
    """A loop that carries a ``[2048, 8512]`` float32 kernel (granite's
    ``in_proj``: column-major on the device), trains it and sums it, with
    the reset and the sum on flags, as the lane loop has them. With selects
    the kernel is column-major throughout. Under ``_on_flag`` too: no
    row-major value of that shape anywhere in the compiled program, and the
    temporaries hold no copy of it. Under a plain ``lax.cond`` the branches
    take it row-major, the carry turns, and two copies (2 x 70 MB) appear:
    why ``_on_flag`` says the layout. When THAT case fails the compiler has
    learnt it, and ``_on_flag`` can go back to ``lax.cond``."""
    from fedml_tpu.parallel.packed import _on_flag

    shape = (2048, 8512)

    def flagged(flag, update, kept, *more):
        if form == "select":
            return jnp.where(flag > 0, update(kept, *more), kept)
        if form == "on_flag":
            return _on_flag(flag, update, kept, *more)
        return jax.lax.cond(flag > 0, update, lambda kept, *more: kept,
                            kept, *more)

    def loop(w0, x, reset, emit):
        def step(i, carry):
            w, acc = carry
            w = flagged(reset[i], lambda w, w0: w0, w, w0)
            w = w - 0.1 * jax.grad(lambda w: jnp.sum(jnp.tanh((
                x @ w.astype(jnp.bfloat16)).astype(jnp.float32))))(w)
            return w, flagged(emit[i], lambda acc, w: acc + emit[i] * w,
                              acc, w)
        return jax.lax.fori_loop(0, 8, step, (w0, jnp.zeros_like(w0)))

    def sd(dtype, *s):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    compiled = jax.jit(loop).lower(
        sd(jnp.float32, *shape), sd(jnp.bfloat16, 512, shape[0]),
        sd(jnp.float32, 8), sd(jnp.float32, 8)).compile()
    text = compiled.as_text()
    row_major = len(re.findall(r"f32\[2048,8512\]\{1,0", text))
    assert len(re.findall(r"f32\[2048,8512\]\{0,1", text)) > 20
    assert text.count(" conditional(") == (0 if form == "select" else 2)
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    one_copy = 4 * shape[0] * shape[1]
    if form == "plain_cond":
        assert row_major > 0 and temporaries >= 2 * one_copy
    else:
        assert row_major == 0 and temporaries < one_copy


# -- one of 64 chips' share of the hybrid state-space / latent-MoE decoder
#    (nemotron3_super, PR 44): shapes no cell had run ---------------------------

def test_position_free_attention_compiles_at_four_heads_over_one(one_chip,
                                                                 no_cache):
    """4 query heads over ONE key-value head of 128 channels (a chip's
    eighth of 32 over 2), 4,096 positions, one sequence, no rotary: the
    forward kernel and the one backward kernel, ``H`` 4 and ``G`` 1."""
    import importlib

    att = importlib.import_module("fedml_tpu.ops.attention")

    def step(q, k, v, c):
        return jax.grad(lambda q, k, v: jnp.sum(att.attention(
            q, k, v, impl="pallas", block_q=1024, block_k=1024).astype(
                jnp.float32) * c), argnums=(0, 1, 2))(q, k, v)

    def sd(h):
        return jax.ShapeDtypeStruct((1, h, 4096, 128), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(sd(4), sd(1), sd(1), sd(4)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9
    _dq, dk, dv = jax.eval_shape(step, sd(4), sd(1), sd(1), sd(4))
    assert dk.shape == dv.shape == (1, 1, 4096, 128)


def test_state_space_mixer_compiles_at_a_groups_share(one_chip, no_cache):
    """One group's 16 heads of 64 over a state of 128 at hidden 4,096: a
    joint projection of 2,320 columns (1,024 + 1,024 + 256 + 16), issued as
    a product a consumer as at 8,512: no array of all 2,320 columns but the
    ONE kernel and its gradient."""
    from fedml_tpu.models.transformer import Mamba2Mixer

    mixer = Mamba2Mixer(16, 64, 128, eps=1e-5, dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((1, 4096, 4096), jnp.bfloat16, sharding=one_chip)
    v = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), u))

    def step(v, u, c):
        return jax.grad(lambda p, u: jnp.sum(mixer.apply(
            {**v, "params": p}, u).astype(jnp.float32) * c), argnums=(0, 1))(
                v["params"], u)

    compiled = jax.jit(step).lower(v, u, u).compile()
    text = compiled.as_text()
    wide = set(re.findall(r" = \(?(\w+\[[\d,]*2320\])", text))
    assert wide and all(shape.endswith("[4096,2320]") for shape in wide), wide
    assert " while(" in text and "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    gp, gu = jax.eval_shape(step, v, u, u)
    assert gp["in_proj"]["kernel"].shape == (4096, 2320)
    assert gp["out_proj"]["kernel"].shape == (1024, 4096)
    assert gu.shape == (1, 4096, 4096)


def test_latent_sparse_layer_compiles_at_published_widths(one_chip, no_cache):
    """The sparse sub-layer at the published widths on 4,096 tokens: a
    router of 512 outputs and 22 choices (90,112 pairs), 8 held experts of
    TWO matrices ``[1024, 2688]`` / ``[2688, 1024]`` in a latent between two
    projections, a squared-ReLU shared MLP of 5,376 on the full width: one
    conditional a pass over the four row capacities, two grouped matmuls a
    branch and pass direction (no third matrix), the rows that move 1,024
    wide."""
    from fedml_tpu.models import moe

    layer = moe.SharedRoutedMoe(512, 22, 2688, 1, 5.0, 0, 8, jnp.bfloat16,
                                eps=1e-5, form="relu2", latent=1024,
                                shared_width=5376)
    x = jax.ShapeDtypeStruct((1, 4096, 4096), jnp.bfloat16, sharding=one_chip)
    v = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.key(0), x))
    p = v["params"]
    assert set(p) == {"shared", "latent_in", "latent_out", "router",
                      "e_score_correction_bias", "up", "down"}
    assert p["up"].shape == (8, 1024, 2688) and p["down"].shape == (8, 2688, 1024)
    assert p["shared"]["up"]["kernel"].shape == (4096, 5376)
    assert set(v["counters"]) == {"expert_rows", "steps", "live_units"}

    def step(v, x, c):
        def loss(p, x):
            out, _ = layer.apply({**v, "params": p}, x)
            return jnp.sum((out.astype(jnp.float32) * c) ** 2)
        return jax.grad(loss, argnums=(0, 1))(v["params"], x)

    compiled = jax.jit(step).lower(v, x, x).compile()
    text = compiled.as_text()
    rungs = moe.row_rungs(4096 * 22)
    assert rungs == (11264, 22528, 45056, 90112)
    assert text.count(" conditional(") == 2
    for c in rungs:
        assert f"moe_rows_{c}/" in text
    # the first capacity's rows are [11264, 1024]: a quarter of the bytes a
    # row of the model's width would move
    assert "bf16[11264,1024]" in text and "bf16[11264,4096]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9
    gp, gx = jax.eval_shape(step, v, x, x)
    assert gp["latent_in"]["kernel"].shape == (4096, 1024)
    assert gx.shape == (1, 4096, 4096)


#: float32 shapes of the nemotron3_super tree beside ``KEPT_SHAPES``
KEPT_SHAPES_LATENT = [
    (4096, 2320), (1024, 4096), (4, 1280), (1280,), (16,), (4096, 512),
    (512,), (4096, 128), (512, 4096), (4096, 1024), (4096, 5376),
    (5376, 4096), (8, 1024, 2688), (8, 2688, 1024), (16384, 4096),
    (4096, 16384), (4096,)]


def test_kept_transposed_is_the_compilers_layout_for_the_latent_tree(
        one_chip, no_cache):
    """``_kept_transposed`` against the compiler's own argument layouts for
    every leaf shape of the one-sub-layer decoder's share (the one-lane
    round's branches are told the layout by it: ``packed._on_flag``)."""
    from fedml_tpu.parallel.packed import _kept_transposed

    compiled = jax.jit(lambda *xs: [x + 1 for x in xs]).lower(*(
        jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
        for s in KEPT_SHAPES_LATENT)).compile()
    for shape, fmt in zip(KEPT_SHAPES_LATENT, compiled.input_formats[0]):
        order = tuple(fmt.layout.major_to_minor)
        plain = tuple(range(len(shape)))
        swapped = plain[:-2] + plain[-2:][::-1]
        assert order == (swapped if _kept_transposed(shape) else plain), shape
