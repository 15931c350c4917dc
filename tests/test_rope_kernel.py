"""The one-pass rotary kernel (ops/rope.py), in interpret mode, against
the formula it replaces on a TPU (`models/transformer.rope`), and the
rule that picks between them."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from edl_tpu.models import transformer as tfm
from edl_tpu.ops import rope as kernel

THETA = 1e6


def _x(shape, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _positions(which: str, s: int):
    return {"index": None,
            "two_copies": jnp.arange(s) % (s // 2),
            "far": jnp.arange(s) * 7 + 100_000}[which]


def _ulps(got, want, x=None):
    """|got - want| in units of the last place of ``want``'s type, at
    ``want``'s size; with ``x`` (the rotation's input) at the size of
    the larger of the two products that make a place's result, which
    is where float32's own roundings are once the two nearly cancel."""
    dtype = want.dtype
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    size = np.abs(want)
    if x is not None:
        x = np.abs(np.asarray(x, np.float64))
        size = np.maximum(size, np.maximum(
            x, np.roll(x, x.shape[-1] // 2, -1)))
    fi = jnp.finfo(dtype)
    spacing = 2.0 ** (np.floor(np.log2(np.maximum(size, float(fi.tiny))))
                      - fi.nmant)
    return np.abs(got - want) / spacing


def _cfg(mesh=None, head=128):
    return tfm.TransformerConfig(
        vocab_size=64, d_model=2 * head, n_heads=2, n_layers=1, d_ff=64,
        max_len=256, pos="rope", rope_theta=THETA, mesh=mesh)


# (B, S, H, D): one head and several, a head of two lane rows, a sequence
# of several blocks (2048 rows a block) and one of a block's half
SHAPES = [(2, 256, 1, 128), (1, 256, 3, 128), (1, 128, 2, 256),
          (1, 4096, 2, 128)]


@pytest.mark.parametrize("positions", ["index", "two_copies", "far"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_forward_is_the_formula_to_one_unit_in_the_last_place(
        shape, dtype, positions):
    x, at = _x(shape, dtype), _positions(positions, shape[1])
    want = tfm.rope(x, THETA, at)
    with kernel.force_interpret_kernel():
        rows = kernel.rows_for(x)
        got = kernel.rotate(x, THETA, at, rows)
    assert rows == min(shape[1], kernel.ROWS * 128 // shape[3])
    assert got.dtype == want.dtype and got.shape == want.shape
    if dtype == jnp.bfloat16:   # a float32 product-sum, fused or not
        assert _ulps(got, want).max() <= 1
        assert (np.asarray(got) != np.asarray(want)).mean() < 1e-3
    else:
        assert _ulps(got, want, x).max() <= 1


@pytest.mark.parametrize("positions", ["index", "two_copies"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("shape", SHAPES[:3], ids=str)
def test_gradient_is_the_formulas(shape, dtype, positions):
    x, g = _x(shape, dtype), _x(shape, jnp.float32, seed=1)
    at = _positions(positions, shape[1])

    def loss(turn):
        return lambda x: jnp.sum(turn(x).astype(jnp.float32) * g)

    want = jax.grad(loss(lambda x: tfm.rope(x, THETA, at)))(x)
    with kernel.force_interpret_kernel():
        rows = kernel.rows_for(x)
        got = jax.grad(loss(lambda x: kernel.rotate(x, THETA, at, rows)))(x)
    assert got.dtype == x.dtype
    assert _ulps(got, want, None if dtype == jnp.bfloat16 else g).max() <= 1


def test_backward_is_the_rotation_by_the_negative_angle():
    """Rotating and then pulling the result back through the rotation
    gives x again: the rotation is orthogonal."""
    x = _x((1, 256, 2, 128), jnp.float32)
    with kernel.force_interpret_kernel():
        y, pull = jax.vjp(lambda x: kernel.rotate(x, THETA, None, 256), x)
        back, = pull(y)
    np.testing.assert_allclose(back, x, atol=2e-6)
    assert float(jnp.abs(y - x).max()) > 1.0


def test_the_blocks_take_the_kernel_where_the_rule_says():
    """`_rope` with the kernel in interpret mode is `rope`, under both
    of the blocks' position indices."""
    x = _x((1, 256, 2, 128), jnp.bfloat16)
    at = jnp.arange(256) % 128
    with kernel.force_interpret_kernel():
        got, got_at = tfm._rope(_cfg(), x), tfm._rope(_cfg(), x, at)
        second = tfm._rope(_cfg(), x[:, 128:])
    assert _ulps(got, tfm.rope(x, THETA)).max() <= 1
    assert _ulps(got_at, tfm.rope(x, THETA, at)).max() <= 1
    # the second copy sits at the first copy's places
    np.testing.assert_array_equal(got_at[:, 128:], second)
    np.testing.assert_array_equal(got_at[:, :128], got[:, :128])


def _said(caplog, x, mesh=None) -> str:
    caplog.clear()
    # the framework's loggers do not propagate: listen on this one
    kernel.log.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=kernel.log.name):
            rows = kernel.rows_for(x, mesh)
    finally:
        kernel.log.removeHandler(caplog.handler)
    assert len(caplog.records) == 1
    text = caplog.records[0].getMessage()
    assert text.startswith(f"rope {tuple(x.shape)}: ")
    assert (rows is None) == ("plain formula" in text)
    return text


def _tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("shape,rows", [
    ((1, 16384, 32, 128), 2048), ((2, 8192, 4, 128), 2048),
    ((4, 4096, 16, 128), 2048), ((1, 8192, 2, 256), 1024),
    ((1, 1536, 2, 256), 512), ((1, 128, 1, 128), 128)], ids=str)
def test_on_a_tpu_whole_blocks_of_128_lane_heads_take_the_kernel(
        monkeypatch, caplog, shape, rows):
    _tpu(monkeypatch)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert kernel.rows_for(x) == rows
    assert _said(caplog, x).endswith(f"pallas kernel, rows a block {rows}")


@pytest.mark.parametrize("shape,why", [
    ((2, 8192, 32, 64), "a head of 64"),        # the hybrid's heads
    ((1, 256, 4, 192), "a head of 192"),
    ((1, 1000, 4, 128), "a sequence of 1000"),
    ((1, 192, 4, 128), "a sequence of 192")], ids=str)
def test_on_a_tpu_other_shapes_take_the_formula(monkeypatch, caplog, shape,
                                                why):
    _tpu(monkeypatch)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert kernel.rows_for(x) is None
    assert why in _said(caplog, x)


def test_off_a_tpu_the_formula(caplog):
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    assert kernel.rows_for(x) is None
    assert "backend cpu" in _said(caplog, x)


@pytest.mark.parametrize("axis", ["tp", "fsdp", "dp"])
def test_on_a_tpu_a_mesh_that_shards_takes_the_formula(monkeypatch, caplog,
                                                       axis):
    _tpu(monkeypatch)
    x = jax.ShapeDtypeStruct((2, 1024, 4, 128), jnp.bfloat16)
    sharded = Mesh(np.array(jax.devices()[:2]), (axis,))
    one = Mesh(np.array(jax.devices()[:1]), (axis,))
    assert kernel.rows_for(x, sharded) is None
    assert f"'{axis}': 2" in _said(caplog, x, sharded)
    assert kernel.rows_for(x, one) == 1024   # the whole sequence


def test_a_model_off_a_tpu_never_meets_the_kernel(monkeypatch):
    """The tests' small models and every CPU world keep the formula:
    the kernel's entry raises if the blocks reach it."""
    def refuse(*a, **kw):
        raise AssertionError("the kernel on a CPU")
    monkeypatch.setattr(kernel, "rotate", refuse)
    cfg = _cfg(head=16)
    model = tfm.Transformer(cfg)
    tokens = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens, train=False)
    model.apply(variables, tokens, train=False)
