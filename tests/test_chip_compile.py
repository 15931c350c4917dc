"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside JAX and compiles for a topology
that is only described (`jax.experimental.topologies`). It refuses what
interpret-mode Pallas lets through: a block off the dtype's tile, more
VMEM than a kernel may use, a scalar stored to VMEM, a step that does
not fit the chip. Every Pallas kernel of `edl_tpu/ops/` is compiled
here with ``interpret=False`` at the shapes the chip runs them at
(LM-large attention, a 4 MiB optimizer bucket and a ragged one), and
so is the whole LM-large train step. Nothing runs, so this says nothing
about results or times: `chip_smoke.py` does, on the chip.

Only one process may load the TPU library, so everything here happens
in the test's own process, inside fixtures (never at import), and in
this one file.
"""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import SingleDeviceSharding

from edl_tpu.models.transformer import (Transformer, TransformerConfig,
                                        lm_loss_fused)
from edl_tpu.models import transformer as tfm
from edl_tpu.ops import opt_kernels as ok
from edl_tpu.ops import pack
from edl_tpu.ops import rope as rope_kernel
from edl_tpu.train.state import TrainState
from edl_tpu.train.step import make_train_step

BUCKET_ROWS = 8192            # 4 MiB of fp32 in 128-lane rows
RAGGED_ROWS = 8192 + 37       # ends mid-block and mid-(32,128)-tile
F32, I8 = jnp.float32, jnp.int8
# the package re-exports the function under the module's name
fa = importlib.import_module("edl_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without a chip (the next one warns
    and compiles again): keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def placed(chip, tree):
    """Abstract arguments like ``tree``, placed on ``chip``."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        tree)


def compile_for(chip, fn, *shapes):
    """Lower ``fn`` on abstract arguments placed on ``chip``; compile."""
    return jax.jit(fn).lower(*placed(chip, shapes)).compile()


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# (batch, seq, heads, head dim): LM-large, the d_model-1024 shape, and
# the benchmark's cells: d8, a chip of fsdp4, OLMoE, the hybrid's one
# attention layer (as many key/value heads as query heads here: the
# grouped shapes follow below); a fifth entry
# is a sliding window: the afmoe share's global layer and its sliding ones
FLASH_SHAPES = [(8, 1024, 16, 128), (16, 1024, 16, 64),
                (6, 2048, 16, 128), (2, 2048, 16, 128),
                (4, 4096, 16, 128), (2, 8192, 32, 64),
                (2, 8192, 32, 128), (2, 8192, 32, 128, 2048)]


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_flash_kernels_compile(one_chip, no_persistent_cache, kernel,
                               shape):
    shape, window = shape[:4], (*shape[4:], None)[0]
    b, s, h, d = shape
    blk = fa._fit_block(s, 512)
    kw = dict(blk_q=blk, blk_k=blk, scale=d ** -0.5, causal=True,
              interpret=False, window=window)
    x = sds(shape, jnp.bfloat16)
    lse = sds((b * h, s), F32)
    if kernel == "fwd":
        compiled = compile_for(one_chip, functools.partial(fa._fwd, **kw),
                               x, x, x)
        assert custom_calls(compiled) == 1
    else:  # dK/dV and dQ: two kernels
        compiled = compile_for(
            one_chip,
            lambda q, k, v, o, lse, do, dlse: fa._bwd_pallas(
                q, k, v, o, lse, do, dlse=dlse, **kw),
            x, x, x, x, lse, x, lse)
        assert custom_calls(compiled) == 2


# one row of the block-diffusion cell: a copy's 8,192 positions, the
# clean copy's mask and the noised queries' (strictly earlier blocks)
@pytest.mark.parametrize("strict", [False, True], ids=["own", "before"])
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_flash_kernels_compile_by_block_index(one_chip, no_persistent_cache,
                                              kernel, strict):
    b, s, h, d = shape = (1, 8192, 32, 128)
    kw = dict(blk_q=512, blk_k=512, scale=d ** -0.5, causal=True,
              interpret=False, blocks=(4, strict))
    x = sds(shape, jnp.bfloat16)
    lse = sds((b * h, s), F32)
    if kernel == "fwd":
        compiled = compile_for(one_chip, functools.partial(fa._fwd, **kw),
                               x, x, x)
        assert custom_calls(compiled) == 1
    else:
        compiled = compile_for(
            one_chip,
            lambda q, k, v, o, lse, do, dlse: fa._bwd_pallas(
                q, k, v, o, lse, do, dlse=dlse, **kw),
            x, x, x, x, lse, x, lse)
        assert custom_calls(compiled) == 2


# grouped-query attention as the cells run it, the key/value heads
# unrepeated: (batch, seq, heads, key/value heads, head dim, mask). The
# afmoe share's sliding layers, a row of the block-diffusion cell, the
# hybrid's one attention layer
GROUPED_SHAPES = [(2, 8192, 32, 4, 128, {"window": 2048}),
                  (1, 8192, 32, 4, 128, {"blocks": (4, True)}),
                  (2, 8192, 32, 8, 64, {})]


@pytest.mark.parametrize("shape", GROUPED_SHAPES, ids=str)
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_flash_kernels_compile_with_a_key_value_head_by_index(
        one_chip, no_persistent_cache, kernel, shape):
    b, s, h, kv, d, mask = shape
    kw = dict(blk_q=512, blk_k=512, scale=d ** -0.5, causal=True,
              interpret=False, **mask)
    q, k = sds((b, s, h, d), jnp.bfloat16), sds((b, s, kv, d), jnp.bfloat16)
    lse = sds((b * h, s), F32)
    if kernel == "fwd":
        compiled = compile_for(one_chip, functools.partial(fa._fwd, **kw),
                               q, k, k)
        assert custom_calls(compiled) == 1
    else:
        compiled = compile_for(
            one_chip,
            lambda q, k, v, o, lse, do, dlse: fa._bwd_pallas(
                q, k, v, o, lse, do, dlse=dlse, **kw),
            q, k, k, q, lse, q, lse)
        assert custom_calls(compiled) == 2
        assert [x.shape for x in compiled.out_info] == [
            q.shape, k.shape, k.shape]
        # nothing of a whole (B, S, H, D) is made from k or v
        assert "broadcast(" not in compiled.as_text()


# latent attention at the joyai cell's shape: keys and queries of 192
# (no multiple of the 128 lanes, contracted as they are), values of 128
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_flash_kernels_compile_at_a_value_head_size_of_its_own(
        one_chip, no_persistent_cache, kernel):
    b, s, h, d, dv = 2, 8192, 32, 192, 128
    kw = dict(blk_q=512, blk_k=512, scale=d ** -0.5, causal=True,
              interpret=False)
    qk, vo = sds((b, s, h, d), jnp.bfloat16), sds((b, s, h, dv), jnp.bfloat16)
    lse = sds((b * h, s), F32)
    if kernel == "fwd":
        compiled = compile_for(one_chip, functools.partial(fa._fwd, **kw),
                               qk, qk, vo)
        assert custom_calls(compiled) == 1
        o, _ = compiled.out_info
        assert o.shape == (b, s, h, dv)
    else:
        compiled = compile_for(
            one_chip,
            lambda q, k, v, o, lse, do, dlse: fa._bwd_pallas(
                q, k, v, o, lse, do, dlse=dlse, **kw),
            qk, qk, vo, vo, lse, vo, lse)
        assert custom_calls(compiled) == 2
        assert [x.shape[-1] for x in compiled.out_info] == [d, d, dv]


# q of the block-diffusion cell and k of the afmoe share's: the widest
# and the narrowest call of `ops/rope.py` a cell makes
ROPE_SHAPES = [(1, 16384, 32, 128), (2, 8192, 4, 128)]


@pytest.mark.parametrize("shape", ROPE_SHAPES, ids=str)
@pytest.mark.parametrize("name", ["rope_fwd", "rope_bwd"])
def test_rope_kernel_compiles(one_chip, no_persistent_cache, name, shape):
    rows = rope_kernel.ROWS
    tables = sds((shape[1], shape[3]), F32)
    compiled = compile_for(
        one_chip, functools.partial(rope_kernel._call, rows=rows,
                                    interpret=False, name=name),
        sds(shape, jnp.bfloat16), tables, tables)
    assert custom_calls(compiled) == 1
    assert f"%{name}" in compiled.as_text()


@pytest.mark.parametrize("shape", ROPE_SHAPES, ids=str)
def test_rope_and_its_gradient_are_two_passes_in_bfloat16(
        one_chip, no_persistent_cache, monkeypatch, shape):
    """`jax.grad` through the blocks' rope on a TPU: one Mosaic call a
    direction and nothing of the formula's float32 halves (XLA wrote a
    head's two halves, `f32[..., 64]`, to HBM between three passes)."""
    # the rule asks the backend which path to build
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig(vocab_size=64, d_model=128, n_heads=1,
                            n_layers=1, d_ff=64, max_len=shape[1],
                            pos="rope", rope_theta=1e6)
    at = jnp.arange(shape[1]) % (shape[1] // 2)

    def value_and_grad(x, g):
        return jax.value_and_grad(lambda x: jnp.sum(
            tfm._rope(cfg, x, at).astype(F32) * g))(x)

    text = compile_for(one_chip, value_and_grad, sds(shape, jnp.bfloat16),
                       sds(shape, jnp.bfloat16)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert "%rope_fwd" in text and "%rope_bwd" in text
    b, s, h, d = shape
    for halves in (f"f32[{b},{s},{h},{d // 2}]", f"f32[{b},{h},{s},{d // 2}]",
                   f"f32[{b * h},{s},{d // 2}]"):
        assert halves not in text


def test_chunked_scan_compiles_at_the_hybrid_cells_shape(
        one_chip, no_persistent_cache, monkeypatch):
    """`ops/ssd.py` forward and written-out backward for one mamba layer
    of benchmark/configs/granite-4.0-h-micro-p1v4.json (2 x 8192
    tokens, 64 heads x 64, state 128, chunk 256) on the path a TPU
    takes: two Mosaic calls, `ssd_fwd` and `ssd_bwd`, and nothing of
    size (256, 256) a chunk and head outside them. The compiler counts
    403,459,584 B of temporaries here (1.6 GB of decays and masked
    products as einsums): the states that enter the chunks (134 MB) and
    the copies that bring this test's (B, S, H, P) arguments into the
    kernels' (B, S, H P) layout, which a mixer's own reshapes do not
    need."""
    from edl_tpu.ops import ssd
    # the dispatch asks the backend, and the backend here is the CPU
    monkeypatch.setattr(ssd, "_path", lambda q, h, p, n: (
        "pallas kernel, compiled", False))
    b, s, h, p, n = 2, 8192, 64, 64, 128
    bf16 = jnp.bfloat16

    def loss(x, dt, a, bm, cm):
        y = ssd.ssd_scan(x, dt, a, bm, cm, chunk=256)
        return jnp.sum(y.astype(F32) ** 2)
    compiled = compile_for(
        one_chip, jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
        sds((b, s, h, p), bf16), sds((b, s, h), F32), sds((h,), F32),
        sds((b, s, n), bf16), sds((b, s, n), bf16))
    text = compiled.as_text()
    assert custom_calls(compiled) == 2
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.45e9


@pytest.mark.parametrize("stage", ["conv", "gate_norm"])
def test_mixer_stages_compile_at_the_hybrid_cells_shape(
        one_chip, no_persistent_cache, monkeypatch, stage):
    """`ops/ssm_stages.py`, forward and written-out backward of one
    stage of one mamba layer of the same configuration (the projection
    (2, 8192, 8512), read by block index) on the path a TPU takes: the
    conv as two calls a direction (the x columns, the B|C columns), the
    gate and norm as one, inside the kernels' VMEM (the backward of the
    conv's x columns holds five double-buffered (1024, 1024) blocks:
    over the default 16 MiB), and temporaries of a few bfloat16
    activations."""
    from edl_tpu.ops import ssm_stages
    # the dispatch asks the backend, and the backend here is the CPU
    monkeypatch.setattr(ssm_stages, "_path", lambda plan: (
        "pallas kernel, compiled", False))
    b, s, h, p, n = 2, 8192, 64, 64, 128
    inner, bf16 = h * p, jnp.bfloat16
    proj = sds((b, s, 2 * inner + 2 * n + h), bf16)

    if stage == "conv":
        def loss(proj, taps, bias):
            outs = ssm_stages.conv(proj, taps, bias, start=inner,
                                   sizes=(inner, n, n))
            return sum(jnp.sum(t.astype(F32) ** 2) for t in outs)
        args = (proj, sds((4, inner + 2 * n), F32), sds((inner + 2 * n,), F32))
        names, calls = ("ssm_conv_fwd", "ssm_conv_bwd"), 4
    else:
        def loss(y, x, proj, skip, scale):
            return jnp.sum(ssm_stages.gate_norm(
                y, x, proj, skip, scale, eps=1e-5).astype(F32) ** 2)
        args = (sds((b, s, h, p), bf16), sds((b, s, h, p), bf16), proj,
                sds((h,), F32), sds((inner,), F32))
        names, calls = ("ssm_gate_norm_fwd", "ssm_gate_norm_bwd"), 2
    compiled = compile_for(one_chip, jax.grad(
        loss, argnums=tuple(range(len(args)))), *args)
    text = compiled.as_text()
    assert custom_calls(compiled) == calls
    assert all(name in text for name in names)
    # conv 415,365,120 B, gate and norm 683,800,576 B: the stage's
    # outputs and this loss's cotangents of them, in bfloat16
    assert compiled.memory_analysis().temp_size_in_bytes < (
        0.45e9 if stage == "conv" else 0.72e9)


@pytest.mark.parametrize("rows", [BUCKET_ROWS, RAGGED_ROWS])
def test_pack_kernel_compiles(one_chip, no_persistent_cache, rows):
    compiled = compile_for(
        one_chip, functools.partial(pack._pack_pallas, interpret=False),
        sds((rows, 128), F32))
    assert custom_calls(compiled) == 2  # abs-max pass, quantize pass


def _moment(rows, quant):
    plane, scalar = sds((rows, 128), I8), sds((), F32)
    return (sds((rows, 128), F32) if quant == "off"
            else ok.QPlane(plane, scalar, plane, scalar))


@pytest.mark.parametrize("rows", [BUCKET_ROWS, RAGGED_ROWS])
@pytest.mark.parametrize("quant", ok.QUANT_MODES)
@pytest.mark.parametrize("optimizer", ok.OPTIMIZERS)
def test_optimizer_kernels_compile(one_chip, no_persistent_cache,
                                   optimizer, quant, rows):
    p, s, m = sds((rows, 128), F32), sds((), F32), _moment(rows, quant)
    if optimizer == "sgdm":
        compiled = compile_for(
            one_chip, functools.partial(ok._sgdm_pallas, mu=0.9, wd=1e-4,
                                        quant=quant, interpret=False),
            p, p, m, s)
        planes = 1
    else:
        compiled = compile_for(
            one_chip, functools.partial(ok._adam_pallas, b1=0.9, b2=0.999,
                                        eps=1e-8, wd=0.01, quant=quant,
                                        interpret=False),
            p, p, m, m, s, s, s)
        planes = 2
    # the update pass, then two requant passes per quantized moment
    assert custom_calls(compiled) == (1 if quant == "off"
                                      else 1 + 2 * planes)


def test_lm_large_train_step_compiles(one_chip, no_persistent_cache,
                                      monkeypatch):
    """The step `lm_train --bf16 --fused-loss` builds at LM-large,
    for one v5e chip: flash kernels in, and it fits 16 GB."""
    # the program asks the backend which attention to build; here the
    # answer is steered in the test, the program gets no option for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig(vocab_size=32768, d_model=2048, n_heads=16,
                            n_layers=8, d_ff=8192, max_len=1024,
                            dtype=jnp.bfloat16)
    model = Transformer(cfg)
    assert cfg.use_flash(1024)

    def create():
        from flax.core import meta
        variables = meta.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1024), jnp.int32),
            train=False))
        return TrainState.create(apply_fn=model.apply,
                                 params=variables["params"],
                                 tx=optax.adamw(3e-4, weight_decay=0.01))

    state = jax.eval_shape(create)
    batch = {"tokens": sds((8, 1024), jnp.int32)}
    step = make_train_step(lm_loss_fused, donate=True)  # jitted itself
    compiled = step.lower(placed(one_chip, state),
                          placed(one_chip, batch)).compile()
    assert custom_calls(compiled) == 3 * cfg.n_layers  # fwd, dK/dV, dQ
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 16e9, (mem.argument_size_in_bytes,
                         mem.temp_size_in_bytes)


def test_olmoe_d1_train_step_compiles(one_chip, no_persistent_cache,
                                      monkeypatch):
    """The step `lm_train --arch olmoe --bf16 --fused-loss` builds for
    benchmark/configs/olmoe-1b-7b-d1.json (published widths, one layer,
    4 x 4096 tokens), for one v5e chip: the three flash kernels, the
    seven grouped expert matmuls as XLA's own Mosaic kernel (three
    tables forward; d-lhs and d-rhs of gate|up as one product each and
    of down: nine while the backward took gate and up apart; a dense
    fallback would be 64 times the FLOPs), and it fits 16 GB beside
    7.5 GB of state."""
    from edl_tpu.models.transformer import olmoe_config
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = olmoe_config(n_layers=1, dtype=jnp.bfloat16)
    model = Transformer(cfg)

    def create():
        from flax.core import meta
        variables = meta.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4096), jnp.int32),
            train=False))
        return TrainState.create(apply_fn=model.apply,
                                 params=variables["params"],
                                 tx=optax.adamw(4e-4, weight_decay=0.01))

    state = jax.eval_shape(create)
    batch = {"tokens": sds((4, 4096), jnp.int32)}
    step = make_train_step(functools.partial(
        lm_loss_fused, aux_weight=cfg.moe_aux_weight,
        z_weight=cfg.moe_z_weight), donate=True)
    compiled = step.lower(placed(one_chip, state),
                          placed(one_chip, batch)).compile()
    text = compiled.as_text()
    grouped = sum("%ragged-dot-none" in ln.split(" = ")[0]
                  for ln in text.splitlines())
    assert grouped == 7, grouped
    assert sum(f"%{name}" in text for name in
               ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")) == 3
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 12e9 < held < 14.5e9, (mem.argument_size_in_bytes,
                                  mem.temp_size_in_bytes)


def test_a_shares_expert_layer_moves_the_buffer_no_more_than_it_must(
        one_chip, no_persistent_cache, monkeypatch):
    """One expert layer, forward and backward, at the share cells' shape
    (SDAR's: 16,384 rows, top-8 of 128 experts, 16 held, d 2,048, f 768;
    T*k = 131,072 rows in the buffer), compiled for one v5e chip. With
    gate | up joined in the backward: seven grouped matmuls (the product
    at which the backward's `jax.vjp` linearises is dropped); no
    `add_any` over the buffer's rows (two cotangents of the dispatched
    rows, or two halves of d(h) padded and added); and nothing stands
    alone between the products and the activation: of the buffer's rows,
    (T*k, 2f) is written once, by the fusion that makes d(h) whole, and
    (T*k, f) four times: gate, up, the activation and its cotangent (a
    concatenation, a copy or the halves of d(h) written out would each
    be more)."""
    from flax.core import meta
    from edl_tpu.models.transformer import MoEMLP, sdar_config
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, k, d, f = 16384, 8, 2048, 768
    cfg = sdar_config(vocab_size=256, n_layers=1, d_ff=f, n_experts=128,
                      moe_top_k=k, experts_held=16, dtype=jnp.bfloat16)
    layer = MoEMLP(cfg)
    x = sds((2, t // 2, d), jnp.bfloat16)
    params = jax.eval_shape(lambda: meta.unbox(layer.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"])

    def gradients(params, x, dy):
        def loss(params, x):
            y = layer.apply({"params": params}, x,
                            mutable=["intermediates"])[0]
            return jnp.sum(y.astype(F32) * dy.astype(F32))
        return jax.grad(loss, argnums=(0, 1))(params, x)

    text = compile_for(one_chip, gradients, params, x, x).as_text()
    rows = t * k
    # ENTRY's instructions that write something: name, result(s), operation
    written = [m.groups() for m in re.finditer(
        r"^\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w-]+)\(",
        text[text.index("\nENTRY"):], re.M)
        if m[3] not in ("get-tuple-element", "bitcast", "parameter")]
    assert sum(n.startswith("%ragged-dot-none") for n, _, _ in written) == 7
    assert not [n for n, shapes, _ in written
                if "add_any" in n and f"[{rows}," in shapes]

    def results(width):
        return [(n, op) for n, shapes, op in written
                for _ in range(shapes.count(f"bf16[{rows},{width}]"))]
    assert [op for _, op in results(2 * f)] == ["fusion"], results(2 * f)
    assert sorted(op for _, op in results(f)) == [
        "custom-call", "custom-call", "custom-call", "fusion"], results(f)
