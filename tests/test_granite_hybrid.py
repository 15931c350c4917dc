"""The hybrid state-space model (`models.transformer.
granite_hybrid_config`: Mamba-2 mixers with grouped-query NoPE attention
among them, a dense SwiGLU MLP, a tied head, four multipliers) against
the plain reference `benchmark/reference/granite_hybrid_plain.py`, at a
small size on the CPU with seeded random weights: the chunked scan
(`ops/ssd.py`) and its written-out backward against the recurrence
walked one position at a time, the mixer and the attention layer by
themselves, the whole model's logits, loss and every gradient leaf, the
streamed CE on the tied table against the dense-logits loss, the depth
cut, a save and a restore of the new tree, and the three builders that
were there pinned as they were.

Tolerances. Program and reference both compute in float32 here and
differ in the order of their sums (chunks of matrix products against
one position after the other): differences read 2e-7 to 3e-6 on logits
of size 1 and on the scan's outputs of size 25. `TOL` = 2e-5 on values
of size 1 is five times that; the scan's gradients, sums over every
position (dA over all of them), get ten times `TOL`. In bfloat16 the scan's operands are
rounded (2^-9 a value) and its sums are float32: outputs of size 25
differ by up to 0.2, and `BF16_TOL` bounds the error relative to the
largest value at 2e-2. The controls at the end show what `TOL` refuses:
a chunk's entering state dropped, the attention multiplier read as
1/sqrt(D), a missing residual multiplier and bfloat16 activations each
move the logits by 50 x TOL or more.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import traverse_util
from flax.core import meta

from benchmark.reference import granite_hybrid_plain as plain
from edl_tpu.models import transformer as tfm
from edl_tpu.ops import ssd, ssm_stages
from edl_tpu.train.state import TrainState

TOL = 2e-5
BF16_TOL = 2e-2
VOCAB, SEQ, D, HEADS, KV, FF = 96, 48, 32, 4, 2, 48
SSM = dict(ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=16)
KINDS = ("mamba", "attention", "mamba")
HP = {"n_head": HEADS, "n_kv_head": KV, "eps": 1e-5,
      "attention_multiplier": 0.015625, "embedding_multiplier": 12.0,
      "residual_multiplier": 0.22, "logits_scaling": 8.0}


def small(**changed):
    return dataclasses.replace(tfm.granite_hybrid_config(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_kv_heads=KV,
        n_layers=len(KINDS), d_ff=FF, max_len=SEQ, layer_types=KINDS,
        dtype=jnp.float32, **SSM), **changed)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(11).integers(
        0, VOCAB, (2, SEQ)), jnp.int32)


@pytest.fixture(scope="module")
def tree(tokens):
    """Seeded parameters; scales, the conv's bias and the skip drawn too
    (ones and zeros would hide a term that is forgotten), the table
    wider than its init (logits of size 1, not 0.01)."""
    params = meta.unbox(tfm.Transformer(small()).init(
        jax.random.PRNGKey(5), tokens, train=False))["params"]
    flat = traverse_util.flatten_dict(params)
    rng = np.random.default_rng(17)
    for path, leaf in flat.items():
        if path[-1] in ("scale", "D"):
            flat[path] = jnp.asarray(
                rng.uniform(0.5, 1.5, leaf.shape), jnp.float32)
        if path[-1] == "conv_bias":
            flat[path] = jnp.asarray(
                rng.normal(0, 0.2, leaf.shape), jnp.float32)
        if path[-1] == "embedding":
            flat[path] = leaf * 10.0
    return traverse_util.unflatten_dict(flat)


def program_logits(tree, tokens, **kw):
    return tfm.Transformer(small(**kw)).apply({"params": tree}, tokens,
                                              train=True)


def plain_logits(tree, tokens, hp=HP):
    params = plain.from_program(tree)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([plain.forward(params, row, hp) for row in tokens])


def state_of(tree, **kw):
    return TrainState.create(apply_fn=tfm.Transformer(small(**kw)).apply,
                             params=tree, tx=optax.sgd(0.1))


# -- the scan ---------------------------------------------------------------

def scan_inputs(chunks, dtype, chunk=16, b=2, h=3, p=4, n=8):
    s = chunks * chunk
    k = jax.random.split(jax.random.PRNGKey(chunks), 6)
    x = jax.random.normal(k[0], (b, s, h, p)).astype(dtype)
    bm = jax.random.normal(k[1], (b, s, n)).astype(dtype)
    cm = jax.random.normal(k[2], (b, s, n)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[3], (b, s, h)))
    a = -jnp.exp(jax.random.uniform(k[4], (h,), minval=0.0, maxval=2.7))
    w = jax.random.normal(k[5], (b, s, h, p))
    return (x, dt, a, bm, cm), w


def walked(x, dt, a, bm, cm):
    """The reference's recurrence, a sequence at a time, in float32."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        return jnp.stack([plain.recurrence(
            x[i].astype(f32), dt[i], a, bm[i].astype(f32),
            cm[i].astype(f32)) for i in range(x.shape[0])])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunks", [2, 3])
def test_chunked_scan_is_the_recurrence(chunks, dtype):
    """S of 2 and 3 chunks of 16: the state crosses a boundary once and
    twice."""
    args, _ = scan_inputs(chunks, dtype)
    mine = ssd.ssd_scan(*args, chunk=16)
    ref = walked(*args)
    assert mine.dtype == dtype and mine.shape == args[0].shape
    err = float(jnp.abs(mine.astype(jnp.float32) - ref).max())
    size = float(jnp.abs(ref).max())
    assert err < (BF16_TOL if dtype == jnp.bfloat16 else TOL) * size, err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunks", [2, 3])
def test_chunked_scan_gradients_are_the_recurrences(chunks, dtype):
    """`jax.grad` through the written-out backward against autodiff
    through the recurrence, for x, dt, A, B and C."""
    args, w = scan_inputs(chunks, dtype)
    mine = jax.grad(lambda *t: jnp.sum(
        ssd.ssd_scan(*t, chunk=16).astype(jnp.float32) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    ref = jax.grad(lambda *t: jnp.sum(walked(*t) * w),
                   argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip(("x", "dt", "a", "b", "c"), mine, ref):
        assert g.dtype == r.dtype and g.shape == r.shape
        err = float(jnp.abs(g.astype(jnp.float32)
                            - r.astype(jnp.float32)).max())
        size = float(jnp.abs(r.astype(jnp.float32)).max())
        tol = 3 * BF16_TOL if dtype == jnp.bfloat16 else 10 * TOL
        assert err < tol * size, (name, err, size)


def test_the_references_walk_in_segments_is_the_walk(monkeypatch):
    """The reference keeps a state every `SEGMENT` positions for its
    gradient's sake (the benchmark's checker differentiates it at 8,192
    positions); values and gradients are those of the walk in one piece,
    which is what a length the segment does not divide gets."""
    (x, dt, a, bm, cm), w = scan_inputs(4, jnp.float32, b=1)
    args = (x[0], dt[0], a, bm[0], cm[0])

    def value_and_grads(segment):
        monkeypatch.setattr(plain, "SEGMENT", segment)
        return jax.value_and_grad(lambda *t: jnp.sum(
            plain.recurrence(*t) * w[0]), argnums=(0, 1, 2, 3, 4))(*args)
    (whole, whole_grads), (cut, cut_grads) = (value_and_grads(5),
                                              value_and_grads(16))
    assert float(jnp.abs(whole - cut)) < TOL * abs(float(whole))
    for g, r in zip(cut_grads, whole_grads):
        assert float(jnp.abs(g - r).max()) < TOL * float(jnp.abs(r).max())


def test_scan_refuses_a_ragged_sequence_and_takes_a_short_one():
    args, _ = scan_inputs(2, jnp.float32)
    with pytest.raises(ValueError, match="multiple"):
        ssd.ssd_scan(*args, chunk=24)
    # shorter than a chunk: one chunk of the sequence's length
    short = [t[:, :8] if t.ndim > 1 else t for t in args]
    assert float(jnp.abs(ssd.ssd_scan(*short, chunk=256)
                         - walked(*short)).max()) < 1e-4


@pytest.mark.parametrize("path", ["einsums", "kernels"])
def test_scan_backward_holds_no_square_tensor(path):
    """The residuals are the inputs and one state a chunk and head: no
    (Q, Q) array is saved between the forward and the backward, on the
    einsums' path or on the kernels'."""
    if path == "einsums":
        (args, _), chunk, states = scan_inputs(3, jnp.float32), 16, \
            (2, 3, 3, 4, 8)                     # (B, chunks, H, P, N)
    else:
        (args, _), chunk, states = kernel_inputs(jnp.float32), K_CHUNK, \
            (2, 3, K_HEADS, K_P, K_N)
    with ssd.force_interpret_kernels():
        _, vjp = jax.vjp(lambda *t: ssd.ssd_scan(*t, chunk=chunk), *args)
    saved = [leaf.shape for leaf in jax.tree.leaves(vjp)
             if hasattr(leaf, "shape")]
    assert states in saved
    assert not any(s[-2:] == (chunk, chunk) for s in saved if len(s) >= 2)


# -- the scan's kernels, in interpret mode ------------------------------------
#
# The smallest shape the kernels' tiles take that has several chunks,
# two blocks of heads and batch 2: chunks of 128, a state of 128, 16
# heads of 64. Against the einsums the kernels differ in the order of
# float32 sums alone (their operands are rounded at the same points):
# `TOL` and ten times `TOL` as above, and in bfloat16 one rounding of an
# output (2^-8 of a value) where the float32 sums fall on either side.

K_CHUNK, K_HEADS, K_P, K_N = 128, 16, 64, 128


def kernel_inputs(dtype, chunks=3, decay_at_ends=False):
    (x, dt, a, bm, cm), w = scan_inputs(
        chunks, dtype, chunk=K_CHUNK, h=K_HEADS, p=K_P, n=K_N)
    dt = dt * 0.05                      # step sizes of the published size
    if decay_at_ends:
        # a large decay at every chunk's last position: what a chunk's
        # end state carries on (`d_last`) is then most of d(cs) there
        dt = dt.at[:, K_CHUNK - 1::K_CHUNK].mul(40.0)
    bm, cm = bm / K_N ** 0.25, cm / K_N ** 0.25
    return (x, dt, a, bm, cm), w


def outputs(args, w, chunk=K_CHUNK):
    """y, and the gradients of sum(y * w) for x, dt, A, B and C."""
    y, vjp = jax.vjp(lambda *t: ssd.ssd_scan(*t, chunk=chunk), *args)
    return (y, *vjp(w.astype(y.dtype)))


@pytest.mark.parametrize("decay_at_ends", [False, True],
                         ids=["spread", "decay_at_chunk_ends"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scan_kernels_are_the_einsums(dtype, decay_at_ends):
    """y and the gradients of x, dt, A, B and C through the two Pallas
    kernels, against the einsums on the same inputs."""
    args, w = kernel_inputs(dtype, decay_at_ends=decay_at_ends)
    ref = outputs(args, w)
    with ssd.force_interpret_kernels():
        mine = outputs(args, w)
    for name, g, r in zip(("y", "x", "dt", "a", "b", "c"), mine, ref):
        assert g.dtype == r.dtype and g.shape == r.shape
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        size = float(jnp.abs(r).max())
        tol = 2.0 ** -7 if dtype == jnp.bfloat16 else \
            TOL if name == "y" else 10 * TOL
        assert float(jnp.abs(g - r).max()) < tol * size, (name, size)


@pytest.mark.parametrize("decay_at_ends", [False, True],
                         ids=["spread", "decay_at_chunk_ends"])
def test_scan_kernels_are_the_recurrence(decay_at_ends):
    """The same through the kernels against autodiff through the
    recurrence walked one position at a time, in float32."""
    args, w = kernel_inputs(jnp.float32, chunks=2,
                            decay_at_ends=decay_at_ends)
    with ssd.force_interpret_kernels():
        mine = outputs(args, w)
    ref = (walked(*args), *jax.grad(lambda *t: jnp.sum(walked(*t) * w),
                                    argnums=(0, 1, 2, 3, 4))(*args))
    for name, g, r in zip(("y", "x", "dt", "a", "b", "c"), mine, ref):
        size = float(jnp.abs(r).max())
        assert float(jnp.abs(g - r).max()) < 10 * TOL * size, (name, size)


@pytest.mark.parametrize("hooked, chunk, path", [
    (False, K_CHUNK, "xla einsums"),
    (True, K_CHUNK, "pallas kernel, interpret mode"),
    (True, 96, "xla einsums"),          # a chunk the tiles do not divide
    (True, 16, "xla einsums")])
def test_scan_takes_the_path_it_can_see(hooked, chunk, path):
    """Off a TPU the einsums run, the kernels only under the tests'
    hook and only where the shapes meet their tiles; `describe` says
    which, and the trace holds a kernel or none."""
    import contextlib
    (x, dt, a, bm, cm), _ = kernel_inputs(jnp.float32, chunks=3)
    s = 3 * chunk
    args = (x[:, :s], dt[:, :s], a, bm[:, :s], cm[:, :s])
    hook = ssd.force_interpret_kernels() if hooked \
        else contextlib.nullcontext()
    with hook:
        said = ssd.describe(chunk, K_HEADS, K_P, K_N)
        jaxpr = str(jax.make_jaxpr(
            lambda *t: ssd.ssd_scan(*t, chunk=chunk))(*args))
    assert said.endswith(f"({path})") and f"ssd chunk {chunk}," in said
    assert ("pallas_call" in jaxpr) == path.startswith("pallas")


@pytest.mark.parametrize("seam", ["forward", "backward"])
def test_the_seams_bite_on_the_kernels_path(seam, monkeypatch):
    """What the benchmark's controls patch (`ssd._forward`, and
    `ssd._ssd_bwd` re-registered on `ssd._ssd`) changes the result when
    the kernels run, as it does on the einsums."""
    args, w = kernel_inputs(jnp.float32, chunks=2)
    with ssd.force_interpret_kernels():
        mine = outputs(args, w)
        if seam == "forward":
            forward = ssd._forward
            monkeypatch.setattr(ssd, "_forward", lambda *t: (
                0 * forward(*t)[0], forward(*t)[1]))
            other = outputs(args, w)
            moved = float(jnp.abs(mine[0] - other[0]).max())
        else:
            backward = ssd._ssd_bwd
            ssd._ssd.defvjp(ssd._ssd_fwd, lambda chunk, res, dy: tuple(
                2 * g for g in backward(chunk, res, dy)))
            try:
                other = outputs(args, w)
            finally:
                ssd._ssd.defvjp(ssd._ssd_fwd, ssd._ssd_bwd)
            assert float(jnp.abs(mine[0] - other[0]).max()) == 0.0
            moved = float(jnp.abs(mine[2] - other[2]).max())
    assert moved > 0.1, moved


# -- the mixer's two elementwise stages, in interpret mode --------------------
#
# `ops/ssm_stages.py`: the conv and the gate with its norm as Pallas
# kernels with written-out backwards, against the expressions they
# replace (which run wherever the kernels do not). Shapes: the smallest
# the tiles take with two row blocks of the conv (1,024 rows each), 16
# of the gate's, batch 2, 8 heads of 32 (two column slabs of x) and a
# state of 128. Kernels and expressions differ in the order of float32
# sums alone; in bfloat16 an output sits one rounding apart where the
# sums fall on either side (2^-8 of a value), and the conv's d(input)
# two, because autodiff of the expressions rounds each of the four taps'
# terms before it adds them and the kernel rounds their float32 sum once.

G_S, G_H, G_P, G_N = 2048, 8, 32, 128
G_INNER = G_H * G_P
G_SIZES = (G_INNER, G_N, G_N)


def stage_inputs(dtype, s=G_S, h=G_H, p=G_P, n=G_N):
    """The projection (z | xBC | dt), the scan's y and x, the stages'
    parameters and a cotangent for every output."""
    inner = h * p
    k = jax.random.split(jax.random.PRNGKey(s + h), 10)
    proj = jax.random.normal(k[0], (2, s, 2 * inner + 2 * n + h)
                             ).astype(dtype)
    y, x = (jax.random.normal(q, (2, s, h, p)).astype(dtype) for q in k[1:3])
    return dict(
        proj=proj, y=y, x=x,
        taps=jax.random.uniform(k[3], (4, inner + 2 * n), minval=-0.5,
                                maxval=0.5),
        bias=0.2 * jax.random.normal(k[4], (inner + 2 * n,)),
        skip=jax.random.uniform(k[5], (h,), minval=0.5, maxval=1.5),
        scale=jax.random.uniform(k[6], (inner,), minval=0.5, maxval=1.5),
        w_conv=tuple(jax.random.normal(q, (2, s, z)).astype(dtype)
                     for q, z in zip(k[7:10], (inner, n, n))),
        w_norm=jax.random.normal(k[9], (2, s, inner)).astype(dtype))


def stage_outputs(stage, t):
    """A stage's outputs and every gradient, by name."""
    inner, n = t["scale"].shape[0], t["w_conv"][1].shape[-1]
    if stage == "conv":
        outs, pull = jax.vjp(lambda *a: ssm_stages.conv(
            *a, start=inner, sizes=(inner, n, n)),
            t["proj"], t["taps"], t["bias"])
        # x comes twice, for its two users: both cotangents go in
        assert outs[3] is outs[0] or bool(jnp.all(outs[3] == outs[0]))
        half = t["w_conv"][0] / 2
        return dict(zip(("x", "B", "C", "d_proj", "d_taps", "d_bias"),
                        (*outs[:3], *pull((half, *t["w_conv"][1:], half)))))
    out, pull = jax.vjp(lambda *a: ssm_stages.gate_norm(*a, eps=1e-5),
                        t["y"], t["x"], t["proj"], t["skip"], t["scale"])
    return dict(zip(("out", "dy", "dx", "d_proj", "d_skip", "d_scale"),
                    (out, *pull(t["w_norm"]))))


def worst(got, ref):
    """max |got - ref| over max |ref|, in float32."""
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.abs(got - ref).max()) / float(jnp.abs(ref).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("stage", ["conv", "gate_norm"])
def test_stage_kernels_are_the_expressions(stage, dtype):
    """Outputs and every gradient (the input, the taps, the bias; y, x,
    z, D and the norm's scale) through the kernels against the
    expressions on the same inputs."""
    t = stage_inputs(dtype)
    ref = stage_outputs(stage, t)
    with ssd.force_interpret_kernels():
        mine = stage_outputs(stage, t)
    for name, r in ref.items():
        g = mine[name]
        assert g.dtype == r.dtype and g.shape == r.shape, name
        # sums over 4,096 rows for the parameters' gradients
        tol = 10 * TOL if dtype == jnp.float32 or r.dtype == jnp.float32 \
            else 2.0 ** -6 if (stage, name) == ("conv", "d_proj") \
            else 2.0 ** -7
        assert worst(g, r) < tol, (name, worst(g, r))
    # the cotangent of the projection is zero outside a stage's columns
    cols = slice(G_INNER, 2 * G_INNER + 2 * G_N) if stage == "conv" \
        else slice(0, G_INNER)
    outside = jnp.ones(mine["d_proj"].shape[-1], bool).at[cols].set(False)
    assert float(jnp.abs(mine["d_proj"][..., outside]).max()) == 0.0
    assert float(jnp.abs(mine["d_proj"][..., cols]).min()) > 0.0


def test_conv_kernel_crosses_row_blocks_and_stops_at_a_sequences_start():
    """Rows 1024-1026 take rows 1021-1023 of the block before them (a
    halo read), in both directions; positions 0-2 of the batch's second
    sequence take nothing of the first."""
    t = stage_inputs(jnp.float32)
    ref = stage_outputs("conv", t)
    with ssd.force_interpret_kernels():
        mine = stage_outputs("conv", t)
        rows = ssm_stages._conv_plan(G_S, G_INNER, G_SIZES)["rows"]
        assert G_S == 2 * rows
        for at in (slice(rows - 3, rows + 3), slice(0, 3)):
            for name in ("x", "B", "C", "d_proj"):
                assert worst(mine[name][:, at], ref[name][:, at]) < TOL, name
        # another first sequence: the second's outputs stay, bit for bit
        other = dict(t, proj=t["proj"].at[0].mul(-3.0))
        moved = stage_outputs("conv", other)
        for name in ("x", "B", "C"):
            assert float(jnp.abs(moved[name][1] - mine[name][1]).max()) == 0.0
            assert float(jnp.abs(moved[name][0] - mine[name][0]).max()) > 0.1
        assert float(jnp.abs(moved["d_proj"][1] - mine["d_proj"][1]
                             ).max()) == 0.0
    # and a sequence's first rows see zeros before them: the taps on
    # absent rows drop out
    first = jax.nn.silu(t["bias"] + t["taps"][3] * t["proj"][
        :, 0, G_INNER:2 * G_INNER + 2 * G_N])
    got = jnp.concatenate([mine[k][:, 0] for k in ("x", "B", "C")], -1)
    assert float(jnp.abs(got - first).max()) < TOL


@pytest.mark.parametrize("hooked, s, h, conv_path, norm_path", [
    (False, G_S, G_H, "xla expressions", "xla expressions"),
    (True, G_S, G_H, "pallas kernel, interpret mode",
     "pallas kernel, interpret mode"),
    (True, 24, G_H, "xla expressions", "xla expressions"),   # rows off 16
    (True, G_S, 3, "xla expressions", "xla expressions")])   # 96 lanes
def test_stages_take_the_path_they_can_see(hooked, s, h, conv_path,
                                           norm_path, caplog):
    """Off a TPU the expressions run, the kernels only under the tests'
    hook and only where the shapes meet their tiles; the line the
    trainer logs and the lines a trace logs say which, and the trace
    holds kernels or none."""
    import contextlib
    import logging
    t = stage_inputs(jnp.float32, s=s, h=h)
    hook = ssd.force_interpret_kernels() if hooked \
        else contextlib.nullcontext()
    # the framework's loggers do not propagate: listen on this one
    stages_log = logging.getLogger("edl_tpu.ops.ssm_stages")
    stages_log.addHandler(caplog.handler)
    try:
        with hook:
            said = ssm_stages.describe(s, h, G_P, G_N)
            jaxprs = {stage: str(jax.make_jaxpr(
                lambda t, stage=stage: stage_outputs(stage, t))(t))
                for stage in ("conv", "gate_norm")}
    finally:
        stages_log.removeHandler(caplog.handler)
    assert said.startswith(f"conv ({conv_path}") \
        and f"gate and norm ({norm_path}" in said
    if conv_path.startswith("pallas"):
        assert "blocks 1024 x 256 and x 256" in said \
            and f"blocks 128 x {h * G_P})" in said
    for stage, path in (("conv", conv_path), ("gate_norm", norm_path)):
        assert ("pallas_call" in jaxprs[stage]) == path.startswith("pallas")
    logged = [r.getMessage() for r in caplog.records]
    assert any(m.startswith("ssm conv") and m.endswith(conv_path)
               for m in logged)
    assert any(m.startswith("ssm gate and norm") and m.endswith(norm_path)
               for m in logged)


K_MIXER = dict(ssm_heads=G_H, ssm_head_dim=G_P, ssm_state=G_N, ssm_chunk=128)


@pytest.fixture(scope="module")
def kernel_mixer():
    """A mixer whose scan and stages all meet their kernels' tiles (256
    positions, two chunks), seeded like `tree`."""
    cfg = small(**K_MIXER)
    u = jax.random.normal(jax.random.PRNGKey(8), (2, 256, D))
    params = meta.unbox(tfm.Mamba2Mixer(cfg).init(
        jax.random.PRNGKey(9), u))["params"]
    rng = np.random.default_rng(23)
    params = dict(params, D=jnp.asarray(rng.uniform(0.5, 1.5, (G_H,)),
                                        jnp.float32),
                  conv_bias=jnp.asarray(
                      rng.normal(0, 0.2, (G_INNER + 2 * G_N,)), jnp.float32),
                  norm={"scale": jnp.asarray(
                      rng.uniform(0.5, 1.5, (G_INNER,)), jnp.float32)})
    return cfg, params, u


def test_mixer_is_the_same_through_both_paths(kernel_mixer):
    """The mixer's output and every parameter's gradient with the scan
    and both stages as kernels, against the einsums and expressions."""
    cfg, params, u = kernel_mixer
    w = jax.random.normal(jax.random.PRNGKey(10), u.shape)

    def run():
        return jax.value_and_grad(lambda p, u: jnp.sum(
            tfm.Mamba2Mixer(cfg).apply({"params": p}, u) * w),
            argnums=(0, 1), has_aux=False)(params, u)
    ref = run()
    with ssd.force_interpret_kernels():
        jaxpr = str(jax.make_jaxpr(lambda p, u: tfm.Mamba2Mixer(cfg).apply(
            {"params": p}, u))(params, u))
        mine = run()
    assert sorted(set(re.findall(r"name=(ss\w+)", jaxpr))) == [
        "ssd_fwd", "ssm_conv_fwd", "ssm_gate_norm_fwd"]
    assert set(params) == {"in_proj", "conv_kernel", "conv_bias", "A_log",
                           "dt_bias", "D", "norm", "out_proj"}
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree.leaves(ref)):
        assert worst(g, r) < 20 * TOL, (jax.tree_util.keystr(path),
                                        worst(g, r))


@pytest.mark.parametrize("path", ["expressions", "kernels"])
def test_both_stages_scopes_are_on_the_steps_forward_and_backward(
        kernel_mixer, path):
    """`benchmark/reduce/ssm_scopes.py` sorts device time by scope:
    every operation of the two stages, the kernels' calls among them,
    carries `ssm_conv` or `ssm_gate_norm` in the lowered gradient, in
    the forward and in the backward."""
    import contextlib
    cfg, params, u = kernel_mixer
    hook = ssd.force_interpret_kernels() if path == "kernels" \
        else contextlib.nullcontext()
    with hook:
        text = jax.jit(jax.grad(lambda p, u: jnp.sum(tfm.Mamba2Mixer(
            cfg).apply({"params": p}, u)))).lower(params, u).as_text(
                debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in ("ssm_conv", "ssm_gate_norm"):
        under = [n for n in names if f"/{scope}/" in n]
        assert any("transpose(" in n for n in under), scope
        assert any("transpose(" not in n for n in under), scope


# -- the layers -------------------------------------------------------------

def test_mixer_matches_the_reference(tree):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, D))
    mine = tfm.Mamba2Mixer(small()).apply(
        {"params": tree["block0"]["ssm"]}, x)
    p = plain.from_program(tree)["blocks"][0]["mamba"]
    with jax.default_matmul_precision("highest"):
        ref = jnp.stack([plain.mamba(row, p, HP) for row in x])
    assert float(jnp.abs(mine - ref).max()) < TOL * max(
        1.0, float(jnp.abs(ref).max()))


def test_grouped_query_attention_with_a_free_scale(tree):
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, D))
    p = plain.from_program(tree)["blocks"][1]["attn"]
    assert p["k"].shape == (D, KV * (D // HEADS)) and p["q"].shape == (D, D)
    mine = tfm.Attention(small()).apply(
        {"params": tree["block1"]["attn"]}, x)
    with jax.default_matmul_precision("highest"):
        ref = jnp.stack([plain.attention(row, p, HP) for row in x])
    assert float(jnp.abs(mine - ref).max()) < TOL
    # the scale is the configuration's, not head_dim ** -0.5
    other = tfm.Attention(small(attn_scale=None)).apply(
        {"params": tree["block1"]["attn"]}, x)
    assert float(jnp.abs(mine - other).max()) > 100 * TOL


def test_logits_match_the_reference(tree, tokens):
    ref = plain_logits(tree, tokens)
    assert float(jnp.abs(ref).max()) > 0.5
    diff = jnp.abs(program_logits(tree, tokens) - ref)
    assert float(diff.max()) < TOL


def test_loss_and_every_gradient_leaf_match_the_reference(tree, tokens):
    loss, grads = jax.value_and_grad(lambda p: tfm.lm_loss_fn(
        state_of(tree), p, {"tokens": tokens})[0])(tree)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(plain.train_loss)(
            plain.from_program(tree), tokens, HP)
    assert abs(float(loss) - float(ref_loss)) < TOL

    # `from_program` is a renaming and reshaping: it maps gradients too
    def named(t):
        return {jax.tree_util.keystr(path): leaf for path, leaf in
                jax.tree_util.tree_flatten_with_path(t)[0]}
    mine, theirs = named(plain.from_program(grads)), named(ref_grads)
    assert set(mine) == set(theirs) and len(mine) == len(
        jax.tree.leaves(tree))
    for name in mine:
        scale = max(1e-3, float(jnp.abs(theirs[name]).max()))
        err = float(jnp.abs(mine[name] - theirs[name]).max())
        assert err < 5e-4 * scale, (name, err, scale)


def test_the_references_batch_gradient_is_the_gradient_of_its_loss(
        tree, tokens):
    """`batch_grads` (a row at a time, on the host, as the benchmark's
    checker takes it) against `jax.grad` of `train_loss` in one piece."""
    params = plain.from_program(tree)
    with jax.default_matmul_precision("highest"):
        whole = jax.grad(plain.train_loss)(params, tokens, HP)
    rows = plain.batch_grads(params, np.asarray(tokens), HP)
    for got, ref in zip(jax.tree.leaves(rows), jax.tree.leaves(whole)):
        assert isinstance(got, np.ndarray) and got.shape == ref.shape
        assert float(np.abs(got - np.asarray(ref)).max()) \
            < TOL * max(1e-3, float(jnp.abs(ref).max()))


def test_fused_loss_on_the_tied_table_is_the_dense_logits_loss(tree, tokens):
    state = state_of(tree)
    dense, dm = tfm.lm_loss_fn(state, tree, {"tokens": tokens})
    fused, fm = tfm.lm_loss_fused(state, tree, {"tokens": tokens},
                                  block_rows=32)
    assert abs(float(dense) - float(fused)) < 1e-5
    assert set(dm) == set(fm) == {"ppl"}
    gd = jax.grad(lambda p: tfm.lm_loss_fn(
        state, p, {"tokens": tokens})[0])(tree)
    gf = jax.grad(lambda p: tfm.lm_loss_fused(
        state, p, {"tokens": tokens}, block_rows=32)[0])(tree)
    # the table's gradient holds both of its uses: gather and head
    assert "lm_head" not in tree
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gf)):
        assert float(jnp.abs(a - b).max()) < 1e-5


def test_remat_changes_no_number(tree, tokens):
    plain_run = program_logits(tree, tokens)
    assert float(jnp.abs(program_logits(tree, tokens, remat=True)
                         - plain_run).max()) == 0.0
    ga, gb = (jax.grad(lambda p, r=r: tfm.lm_loss_fn(
        state_of(tree, remat=r), p, {"tokens": tokens})[0])(tree)
        for r in (False, True))
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert float(jnp.abs(a - b).max()) < 1e-6


def test_remat_changes_no_gradient_through_the_flash_call(tree, tokens):
    """As above with attention through ops/flash_attention.py (its XLA
    path here), whose `o` and `lse` a rematerialised block keeps
    (`transformer.KEPT`) and whose backward reads the kept ones."""
    ga, gb = (jax.grad(lambda p, r=r: tfm.lm_loss_fn(
        state_of(tree, remat=r, attention="flash"), p,
        {"tokens": tokens})[0])(tree) for r in (False, True))
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert float(jnp.abs(a - b).max()) < 1e-6


# -- the configuration ------------------------------------------------------

def test_parameter_tree_is_the_sources():
    """One period at the published widths, a quarter of the vocabulary:
    797,850,560 parameters, a mamba layer 76,182,976, the attention
    layer 60,821,504."""
    cfg = tfm.granite_hybrid_config(n_layers=10, vocab_size=25088,
                                    max_len=8192)
    shapes = jax.eval_shape(lambda: meta.unbox(tfm.Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32),
        train=False))["params"])
    flat = traverse_util.flatten_dict(shapes, sep="/")

    def count(prefix):
        return sum(int(np.prod(v.shape)) for k, v in flat.items()
                   if k.startswith(prefix))
    assert count("") == 797_850_560
    assert count("block0/") == 76_182_976
    assert count("block5/") == 60_821_504
    assert {k: v.shape for k, v in flat.items()
            if k.startswith("block0/ssm/")} == {
        "block0/ssm/in_proj/kernel": (2048, 8512),
        "block0/ssm/out_proj/kernel": (4096, 2048),
        "block0/ssm/conv_kernel": (4, 4352),
        "block0/ssm/conv_bias": (4352,), "block0/ssm/dt_bias": (64,),
        "block0/ssm/A_log": (64,), "block0/ssm/D": (64,),
        "block0/ssm/norm/scale": (4096,)}
    assert flat["block5/attn/key/kernel"].shape == (2048, 8, 64)
    assert flat["block5/attn/query/kernel"].shape == (2048, 32, 64)
    assert "lm_head/kernel" not in flat and "pos_embed" not in flat


def test_depth_cut_keeps_the_pattern():
    full = tfm.granite_hybrid_config()
    assert len(full.layer_types) == 40
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    cut = tfm.granite_hybrid_config(n_layers=10)
    assert cut.layer_types == full.layer_types[:10]
    assert cut.layer_types.count("mamba") == 9
    assert "".join(k[0] for k in cut.layer_types) == "mmmmmammmm"


def test_mamba_initialisers_are_the_papers():
    a_log = tfm._ssm_a_log_init(jax.random.PRNGKey(0), (4096,))
    assert 1.0 <= float(jnp.exp(a_log).min()) \
        and float(jnp.exp(a_log).max()) <= 16.0
    dt = jax.nn.softplus(tfm._ssm_dt_bias_init(jax.random.PRNGKey(1),
                                               (4096,)))
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.001
    # log-uniform: half of the draws below the geometric mean 1e-2
    assert 0.45 < float(jnp.mean(dt < 1e-2)) < 0.55


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="layer_types"):
        tfm.TransformerConfig(n_layers=2, layer_types=("mamba",),
                              ssm_heads=2)
    with pytest.raises(ValueError, match="layer_types"):
        tfm.TransformerConfig(n_layers=1, layer_types=("linear",))
    with pytest.raises(ValueError, match="ssm_heads"):
        tfm.TransformerConfig(n_layers=1, layer_types=("mamba",))
    with pytest.raises(ValueError, match="n_kv_heads"):
        tfm.TransformerConfig(n_heads=8, n_kv_heads=3)


@pytest.mark.parametrize("sharded", [False, True])
def test_checkpoint_saves_and_restores_the_hybrid_tree(tmp_path, tree,
                                                       tokens, sharded):
    """`CheckpointManager`, sharded or not, on the new tree (scalars a
    head, the conv's taps, no lm_head) with AdamW's moments: what comes
    back is what went in, leaf for leaf, and it steps on."""
    from edl_tpu.train.checkpoint import CheckpointManager
    from edl_tpu.train.step import make_train_step
    model = tfm.Transformer(small())
    state = TrainState.create(apply_fn=model.apply, params=tree,
                              tx=optax.adamw(1e-2))
    # the fixture's tree is other tests' too: nothing is donated
    step = make_train_step(tfm.lm_loss_fused, donate=False)
    state, _ = step(state, {"tokens": tokens})
    from edl_tpu.train.state import TrainStatus
    manager = CheckpointManager(str(tmp_path), sharded=sharded)
    manager.save(state, TrainStatus(epoch=0, step=1))
    fresh = TrainState.create(apply_fn=model.apply, params=jax.tree.map(
        jnp.zeros_like, tree), tx=optax.adamw(1e-2))
    restored, status = manager.restore(fresh)
    assert status.step == 1
    mine = jax.tree_util.tree_flatten_with_path(state)[0]
    back = jax.tree_util.tree_flatten_with_path(restored)[0]
    assert [p for p, _ in mine] == [p for p, _ in back]
    for (path, a), (_, b) in zip(mine, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    _, metrics = step(restored, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))


# -- what was there stays as it was -----------------------------------------

BUILDERS = {
    "gpt2": (lambda: tfm.TransformerConfig(
        vocab_size=97, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=24, dtype=jnp.float32),
        "130564b301244fca0e17e24bc4b2bc6993ce883233b5045d2f6c170e89af6dfa"),
    "gpt2-moe": (lambda: tfm.TransformerConfig(
        vocab_size=97, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=24, dtype=jnp.float32, moe=True, n_experts=4),
        "1db59d56a1421b6af8d671876db346fb52ce467848c9be0066805497173710df"),
    "olmoe": (lambda: tfm.olmoe_config(
        vocab_size=97, d_model=32, n_heads=4, n_layers=2, d_ff=16,
        max_len=24, n_experts=8, moe_top_k=2, dtype=jnp.float32),
        "cad830564b25647ede8ad1627c211b33adfbc7d82656d8fa28988a76e8db4e9c"),
}


@pytest.mark.parametrize("arch", sorted(BUILDERS))
def test_the_builders_that_were_there_give_the_same_trees(arch):
    """Paths, shapes and seeded values of the parameter tree, and the
    logits on them, as the commit before this file gave them (checksums
    taken there, on this CPU backend)."""
    make, pinned = BUILDERS[arch]
    model = tfm.Transformer(make())
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 97, (2, 24)),
                       jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(7), toks,
                                   train=False))["params"]
    digest = hashlib.sha256()
    for path, leaf in sorted(traverse_util.flatten_dict(
            params, sep="/").items()):
        digest.update(f"{path}{leaf.shape}".encode())
        digest.update(np.asarray(leaf).tobytes())
    digest.update(np.asarray(model.apply(
        {"params": params}, toks, train=True)).tobytes())
    assert digest.hexdigest() == pinned


# -- what the tolerance refuses ---------------------------------------------

@pytest.mark.parametrize("what, least", [
    ("bf16", 3e-3), ("state_dropped", 5e-3), ("attention_scale", 1e-2),
    ("no_residual_multiplier", 1e-1)])
def test_the_tolerance_refuses(tree, tokens, monkeypatch, what, least):
    """What `TOL` must not let through moves the logits by far more."""
    mine = program_logits(tree, tokens)
    if what == "bf16":
        other = program_logits(tree, tokens, dtype=jnp.bfloat16)
    elif what == "state_dropped":
        # the chunked scan without what one chunk hands the next
        forward = ssd._forward

        def forgetful(x, dt, a, b, c):
            _, _, m, u, *_ = ssd._local(x, dt, a, b, c)
            return (ssd._dot("bchij,bcjhp->bcihp", m, u),
                    forward(x, dt, a, b, c)[1])
        monkeypatch.setattr(ssd, "_forward", forgetful)
        other = program_logits(tree, tokens)
    elif what == "attention_scale":
        other = program_logits(tree, tokens, attn_scale=None)
    else:
        other = program_logits(tree, tokens, residual_scale=1.0)
    moved = float(jnp.abs(mine - other.astype(jnp.float32)).max())
    assert moved > least > 50 * TOL, (what, moved)
