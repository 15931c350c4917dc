"""Pallas flash attention vs the dense oracle.

Off-TPU the public API dispatches to compiled XLA blockwise paths, so
every dense-parity test here runs under BOTH dispatch modes via the
`attn_path` fixture: the XLA fallback, and the Pallas kernels forced
through the same custom_vjp path in interpret mode (interpret=True
executes the same kernel body) — block logic, causal skip,
online-softmax accumulation, and both backwards stay covered on CPU.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops.flash_attention import (flash_attention,
                                         force_interpret_kernels)
from edl_tpu.parallel.ring_attention import dense_attention


def _qkv(b=2, s=256, h=4, d=64, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(jax.random.fold_in(key, i),
                                   (b, s, h, d), dtype) for i in range(3))


@pytest.fixture(params=["xla_fallback", "pallas_kernels"])
def attn_path(request):
    """Run a test body under each off-TPU dispatch mode."""
    ctx = (force_interpret_kernels() if request.param == "pallas_kernels"
           else contextlib.nullcontext())
    with ctx:
        yield request.param


class TestForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal, attn_path):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal,
                              block_q=128, block_k=128)
        want = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_uneven_blocks(self, attn_path):
        q, k, v = _qkv(s=512)
        out = flash_attention(q, k, v, block_q=128, block_k=256)
        want = dense_attention(q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_single_block(self, attn_path):
        q, k, v = _qkv(s=128)
        out = flash_attention(q, k, v)  # blocks clamp to S
        np.testing.assert_allclose(out, dense_attention(q, k, v),
                                   atol=2e-5)

    def test_custom_scale(self, attn_path):
        q, k, v = _qkv(s=128)
        out = flash_attention(q, k, v, scale=0.05)
        want = dense_attention(q, k, v, scale=0.05)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_bf16_io(self, attn_path):
        q, k, v = _qkv(s=128, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v)
        assert out.dtype == jnp.bfloat16
        want = dense_attention(q, k, v)
        np.testing.assert_allclose(out.astype(np.float32),
                                   want.astype(np.float32), atol=3e-2)

    def test_shape_validation(self):
        q, k, v = _qkv(s=128)
        with pytest.raises(ValueError, match="mismatch"):
            flash_attention(q, k[:, :64], v)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, k, v, block_q=96)

    def test_awkward_seq_len_auto_blocks(self, attn_path):
        """640 = 5x128: defaults must fall back to a block that divides
        S instead of raising (regression: auto mode crashed on any
        128-multiple that wasn't a 512-multiple)."""
        q, k, v = _qkv(s=640)
        out = flash_attention(q, k, v)  # default block 512 -> fits to 128
        np.testing.assert_allclose(out, dense_attention(q, k, v),
                                   atol=2e-5)

    def test_unknown_attention_config_rejected(self):
        from edl_tpu.models.transformer import TransformerConfig
        with pytest.raises(ValueError, match="unknown attention"):
            TransformerConfig(attention="Flash").use_flash(128)


class TestBackward:
    def test_grads_match_dense(self, attn_path):
        q, k, v = _qkv(s=256)

        def f_flash(q, k, v):
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, block_q=128, block_k=128)))

        def f_dense(q, k, v):
            return jnp.sum(jnp.sin(dense_attention(q, k, v)))

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(a, b, atol=5e-5)

    def test_grads_noncausal(self, attn_path):
        q, k, v = _qkv(s=128)

        def f(fn):
            return jax.grad(lambda q: jnp.sum(
                fn(q, k, v, causal=False) ** 2))(q)

        np.testing.assert_allclose(
            f(lambda q, k, v, causal: flash_attention(q, k, v,
                                                      causal=causal)),
            f(lambda q, k, v, causal: dense_attention(q, k, v,
                                                      causal=causal)),
            atol=5e-5)

    def test_xla_fwd_fallback_matches_pallas_kernel(self):
        """`_fwd_blockwise` (the compiled off-TPU forward) vs the Pallas
        forward kernel in interpret mode: o AND lse, both causalities,
        uneven blocks."""
        from edl_tpu.ops.flash_attention import _fwd, _fwd_blockwise
        for causal in (True, False):
            q, k, v = _qkv(s=256)
            scale = 1.0 / q.shape[-1] ** 0.5
            o_ref, lse_ref = _fwd(q, k, v, blk_q=128, blk_k=64,
                                  scale=scale, causal=causal,
                                  interpret=True)
            o_got, lse_got = _fwd_blockwise(q, k, v, blk=64, scale=scale,
                                            causal=causal)
            np.testing.assert_allclose(o_got, o_ref, atol=5e-6)
            np.testing.assert_allclose(lse_got, lse_ref, atol=5e-6)

    # (S, blk_q, blk_k, head dim, dtype): four and eight blocks a side
    # at 256 (the diagonal pair in 2x2 sub-blocks: full, masked and
    # skipped pieces all occur) and at 128 (the diagonal pair masked
    # whole), unequal blocks either way round (several pairs on the
    # diagonal), head dimensions 64 and 128, float32 and bf16 inputs
    KERNEL_CASES = [
        (256, 128, 64, 64, jnp.float32),
        (512, 128, 256, 64, jnp.float32),
        (512, 128, 128, 128, jnp.float32),
        (1024, 128, 128, 64, jnp.float32),
        (1024, 256, 256, 64, jnp.float32),
        (1024, 256, 256, 128, jnp.bfloat16),
        (2048, 256, 256, 64, jnp.float32),
        (2048, 256, 256, 64, jnp.bfloat16),
        (1024, 512, 512, 128, jnp.bfloat16),
        (1024, 256, 512, 64, jnp.bfloat16),
    ]

    @pytest.mark.parametrize("with_dlse", [False, True],
                             ids=["plain", "dlse"])
    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    @pytest.mark.parametrize(
        "case", KERNEL_CASES,
        ids=lambda c: f"{c[0]}-{c[1]}x{c[2]}-d{c[3]}-{c[4].__name__}")
    def test_pallas_bwd_matches_xla_reference(self, case, causal,
                                              with_dlse):
        """The Pallas forward, dK/dV and dQ kernels vs `_fwd_blockwise`
        / `_bwd_blockwise` (the plain XLA scans), incl. the dlse
        cotangent path. float32 inputs: nothing is cast in the kernels,
        so they agree to float32 rounding; bf16 inputs: the reference
        upcasts them, the kernels hand them to the products as they are
        and round p and ds to bf16."""
        from edl_tpu.ops.flash_attention import (_bwd_blockwise,
                                                 _bwd_pallas, _fwd,
                                                 _fwd_blockwise)
        s, blk_q, blk_k, d, dtype = case
        q, k, v = _qkv(b=1, s=s, h=2, d=d, dtype=dtype)
        scale = 1.0 / d ** 0.5
        kw = dict(scale=scale, causal=causal)
        o, lse = _fwd(q, k, v, blk_q=blk_q, blk_k=blk_k, interpret=True,
                      **kw)
        o_ref, lse_ref = _fwd_blockwise(q, k, v, blk=blk_k, **kw)
        rng = np.random.default_rng(5)
        do = jnp.asarray(rng.normal(size=q.shape), dtype)
        dlse = (jnp.asarray(rng.normal(size=lse.shape), jnp.float32)
                if with_dlse else None)
        ref = _bwd_blockwise(q, k, v, o_ref, lse_ref, do, blk=blk_k,
                             dlse=dlse, **kw)
        got = _bwd_pallas(q, k, v, o_ref, lse_ref, do, blk_q=blk_q,
                          blk_k=blk_k, dlse=dlse, interpret=True, **kw)
        np.testing.assert_allclose(lse, lse_ref, atol=5e-6 if dtype
                                   == jnp.float32 else 2e-2)
        for a, b in zip((o, *got), (o_ref, *ref)):
            assert a.dtype == dtype
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if dtype == jnp.float32:
                np.testing.assert_allclose(a, b, atol=5e-5)
            else:  # bf16 results of values up to a few units
                assert np.abs(a - b).max() <= 3e-2 * np.abs(b).max()

    def test_value_and_grad_jits(self):
        q, k, v = _qkv(s=128)
        f = jax.jit(jax.value_and_grad(
            lambda q: jnp.sum(flash_attention(q, k, v))))
        val, grad = f(q)
        assert np.isfinite(float(val))
        assert grad.shape == q.shape


class TestKernelBodies:
    """What one score block pair costs inside the kernels, read off the
    kernels' own jaxprs at bf16 inputs."""

    @staticmethod
    def _kernel_jaxprs(blk):
        from edl_tpu.ops.flash_attention import _bwd_pallas, _fwd
        q, k, v = _qkv(b=1, s=4 * blk, h=1, d=128, dtype=jnp.bfloat16)
        kw = dict(blk_q=blk, blk_k=blk, scale=0.1, causal=True,
                  interpret=True)

        def both(q, k, v):
            o, lse = _fwd(q, k, v, **kw)
            return _bwd_pallas(q, k, v, o, lse, q, dlse=None, **kw)

        calls = [e for e in TestKernelBodies._walk(
            jax.make_jaxpr(both)(q, k, v).jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == [
            "flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"]
        return [e.params["jaxpr"] for e in calls]

    @staticmethod
    def _walk(jaxpr):
        """Every equation of a jaxpr and of all it holds."""
        for e in jaxpr.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from TestKernelBodies._walk(sub)

    @pytest.mark.parametrize("blk", [128, 256])
    def test_operands_as_they_arrive_and_no_transpose(self, blk):
        for jaxpr in self._kernel_jaxprs(blk):
            eqns = list(self._walk(jaxpr))
            names = {e.primitive.name for e in eqns}
            assert "transpose" not in names
            dots = [e for e in eqns if e.primitive.name == "dot_general"]
            assert dots and all(
                v.aval.dtype == jnp.bfloat16 for e in dots for v in e.invars)
            assert all(e.params["preferred_element_type"] == jnp.float32
                       for e in dots)
            # nothing (blocks of Q, K, V or dO least of all) goes up to
            # float32: the only casts are p and ds going down
            casts = [e for e in eqns
                     if e.primitive.name == "convert_element_type"
                     and e.invars[0].aval.shape]
            assert casts and all(
                e.invars[0].aval.dtype == jnp.float32
                and e.params["new_dtype"] == jnp.bfloat16 for e in casts)

    def test_mask_only_on_the_diagonal(self):
        """At 256 the diagonal pair is unrolled as sub-blocks, so every
        loop body is the unmasked one; at 128 one loop carries the
        mask, the other does not."""
        def masked_loops(jaxpr):
            loops = [e for e in self._walk(jaxpr)
                     if e.primitive.name == "while"]
            return [any(i.primitive.name == "select_n"
                        for sub in jax.core.jaxprs_in_params(e.params)
                        for i in self._walk(sub)) for e in loops]

        for jaxpr in self._kernel_jaxprs(256):
            assert masked_loops(jaxpr) == [False]
            assert sum(e.primitive.name == "select_n"
                       for e in self._walk(jaxpr)) == 2  # sub-diagonals
        for jaxpr in self._kernel_jaxprs(128):
            assert sorted(masked_loops(jaxpr)) == [False, True]

    @pytest.mark.parametrize("args, want", [
        ((2048, 512, 512, True),
         "blocks 512x512, pairs a head: 6 full, 4 on the diagonal, "
         "6 skipped; a diagonal pair as 2x2 of 256: 1 full, 2 masked, "
         "1 skipped"),
        ((4096, 512, 512, True),
         "blocks 512x512, pairs a head: 28 full, 8 on the diagonal, "
         "28 skipped; a diagonal pair as 2x2 of 256: 1 full, 2 masked, "
         "1 skipped"),
        ((640, 128, 128, True),
         "blocks 128x128, pairs a head: 10 full, 5 on the diagonal, "
         "10 skipped, masked whole"),
        ((256, 128, 64, True),
         "blocks 128x64, pairs a head: 2 full, 4 on the diagonal, "
         "2 skipped, masked whole"),
        ((1024, 512, 512, False), "blocks 512x512, pairs a head: 4 full"),
    ], ids=lambda a: "-".join(map(str, a)) if isinstance(a, tuple) else "")
    def test_block_pairs_line(self, args, want):
        from edl_tpu.ops.flash_attention import block_pairs
        assert block_pairs(*args) == want

    def test_trace_logs_its_blocking(self, caplog):
        import logging
        q, k, v = _qkv(b=1, s=512, h=1)
        # the framework's loggers do not propagate: listen on this one
        fa_log = logging.getLogger("edl_tpu.ops.flash_attention")
        fa_log.addHandler(caplog.handler)
        try:
            with force_interpret_kernels():
                jax.grad(lambda q: jnp.sum(flash_attention(
                    q, k, v, block_q=256, block_k=256)))(q)
            flash_attention(q, k, v)
        finally:
            fa_log.removeHandler(caplog.handler)
        text = [r.getMessage() for r in caplog.records]
        pairs = ("blocks 256x256, pairs a head: 1 full, 2 on the diagonal, "
                 "1 skipped; a diagonal pair as 2x2 of 128: 1 full, "
                 "2 masked, 1 skipped")
        assert text == [
            f"flash attention fwd (1, 512, 1, 64): pallas kernel, "
            f"interpret mode; {pairs}",
            f"flash attention bwd (1, 512, 1, 64): pallas kernel, "
            f"interpret mode; {pairs}",
            "flash attention fwd (1, 512, 1, 64): xla blockwise"]


class TestLseOutput:
    def _oracle(self, q, k, v, s):
        scale = 1.0 / q.shape[-1] ** 0.5
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(mask[None, None], sc, -1e30)
        lse = jax.scipy.special.logsumexp(sc, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(sc - lse[..., None]), v)
        return o, lse.transpose(0, 2, 1)

    def test_lse_values(self, attn_path):
        from edl_tpu.ops.flash_attention import flash_attention_lse
        q, k, v = _qkv(s=128)
        o, lse = flash_attention_lse(q, k, v, block_q=64, block_k=64)
        oo, lo = self._oracle(q, k, v, 128)
        np.testing.assert_allclose(o, oo, atol=2e-5)
        np.testing.assert_allclose(lse, lo, atol=2e-5)

    def test_lse_cotangent_flows(self, attn_path):
        """Gradients through BOTH outputs (the ring-combine consumes
        lse differentiably) must match the dense oracle."""
        from edl_tpu.ops.flash_attention import flash_attention_lse
        q, k, v = _qkv(s=128)

        def loss(fn):
            def f(q, k, v):
                o, lse = fn(q, k, v)
                return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        gf = loss(lambda q, k, v: flash_attention_lse(q, k, v,
                                                      block_q=64,
                                                      block_k=64))
        go = loss(lambda q, k, v: self._oracle(q, k, v, 128))
        for a, b in zip(gf, go):
            np.testing.assert_allclose(a, b, atol=5e-5)

    def test_dispatch_modes_agree_exactly_on_shapes(self):
        """The two off-TPU paths must agree to numerical tolerance on a
        multi-block causal case (guards dispatch-dependent drift)."""
        from edl_tpu.ops.flash_attention import flash_attention_lse
        q, k, v = _qkv(s=256)
        o1, l1 = flash_attention_lse(q, k, v, block_q=128, block_k=128)
        with force_interpret_kernels():
            o2, l2 = flash_attention_lse(q, k, v, block_q=128,
                                         block_k=128)
        np.testing.assert_allclose(o1, o2, atol=2e-5)
        np.testing.assert_allclose(l1, l2, atol=2e-5)


class TestTransformerIntegration:
    def test_flash_config_matches_dense_config(self):
        """Same weights, attention='flash' (interpret) vs 'dense'."""
        from edl_tpu.models.transformer import (Transformer,
                                                TransformerConfig)

        kw = dict(vocab_size=128, d_model=64, n_heads=4, n_layers=2,
                  d_ff=128, max_len=128, dtype=jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
        m_dense = Transformer(TransformerConfig(attention="dense", **kw))
        m_flash = Transformer(TransformerConfig(attention="flash", **kw))
        variables = m_dense.init(jax.random.PRNGKey(0), toks, train=False)
        out_d = m_dense.apply(variables, toks, train=False)
        out_f = m_flash.apply(variables, toks, train=False)
        np.testing.assert_allclose(out_d, out_f, atol=1e-4)


class TestWindow:
    """A sliding window: query i sees keys i - window + 1 .. i. The
    kernels visit only the block pairs that hold such a key; the dense
    masked attention is the oracle."""

    # (sequence, q block, k block, window): windows smaller than, equal
    # to and larger than a block and a sub-block, across unequal blocks,
    # of one key, and of the whole sequence and more
    CASES = [(1024, 256, 256, 100), (1024, 256, 256, 128),
             (1024, 256, 256, 256), (1024, 256, 256, 300),
             (1024, 256, 256, 512), (1024, 256, 256, 1000),
             (1024, 128, 128, 200), (1024, 128, 256, 300),
             (1024, 256, 128, 130), (512, 128, 128, 1),
             (512, 128, 128, 512), (512, 128, 128, 4096)]

    @pytest.mark.parametrize("s, blk_q, blk_k, window", CASES,
                             ids=lambda v: str(v))
    def test_forward_and_backward_match_dense(self, s, blk_q, blk_k,
                                              window, attn_path):
        q, k, v = _qkv(b=1, s=s, h=2)

        def loss(fn, **kw):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, **kw)))
        kw = dict(block_q=blk_q, block_k=blk_k, window=window)
        np.testing.assert_allclose(
            flash_attention(q, k, v, **kw),
            dense_attention(q, k, v, window=window), atol=2e-5)
        got = jax.grad(loss(flash_attention, **kw), argnums=(0, 1, 2))(
            q, k, v)
        want = jax.grad(loss(dense_attention, window=window),
                        argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-4)

    def test_a_window_is_not_the_causal_triangle(self):
        q, k, v = _qkv(b=1, s=512, h=1)
        assert float(jnp.max(jnp.abs(
            dense_attention(q, k, v, window=100)
            - dense_attention(q, k, v)))) > 0.1

    @pytest.mark.parametrize("path", ["xla_fallback", "pallas_kernels"])
    def test_no_window_is_todays_output_to_the_bit(self, path):
        """`window=None`, and a window no query can fill, lower to the
        kernels as they were: the same jaxpr, the same numbers."""
        from edl_tpu.ops.flash_attention import _bwd_pallas, _fwd
        q, k, v = _qkv(b=1, s=512, h=2)
        ctx = (force_interpret_kernels() if path == "pallas_kernels"
               else contextlib.nullcontext())
        with ctx:
            plain = flash_attention(q, k, v, block_q=128, block_k=128)
            none = flash_attention(q, k, v, block_q=128, block_k=128,
                                   window=None)
            wide = flash_attention(q, k, v, block_q=128, block_k=128,
                                   window=512)
        assert np.array_equal(plain, none) and np.array_equal(plain, wide)
        kw = dict(blk_q=128, blk_k=128, scale=0.1, causal=True,
                  interpret=True)

        def both(window):
            def fn(q, k, v):
                o, lse = _fwd(q, k, v, window=window, **kw)
                return _bwd_pallas(q, k, v, o, lse, q, dlse=None,
                                   window=window, **kw)
            return str(jax.make_jaxpr(fn)(q, k, v))
        assert both(None) == str(jax.make_jaxpr(
            lambda q, k, v: _bwd_pallas(
                q, k, v, *_fwd(q, k, v, **kw), q, dlse=None, **kw))(q, k, v))
        assert both(200) != both(None)

    def test_the_band_skips_blocks_on_both_sides(self):
        """At the cell's blocking (equal blocks, the window four of
        them) every kernel has three loops and no more: whole pairs
        unmasked, the band's far edge under its mask, and in the dK/dV
        kernel the mirror image; the diagonal pair is unrolled."""
        from edl_tpu.ops.flash_attention import _bwd_pallas, _fwd
        q, k, v = _qkv(b=1, s=2048, h=1, d=128, dtype=jnp.bfloat16)
        kw = dict(blk_q=256, blk_k=256, scale=0.1, causal=True,
                  interpret=True, window=1024)

        def both(q, k, v):
            o, lse = _fwd(q, k, v, **kw)
            return _bwd_pallas(q, k, v, o, lse, q, dlse=None, **kw)
        walk = TestKernelBodies._walk
        calls = [e for e in walk(jax.make_jaxpr(both)(q, k, v).jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == [
            "flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"]
        for call in calls:
            loops = [e for e in walk(call.params["jaxpr"])
                     if e.primitive.name == "while"]
            masked = [any(i.primitive.name == "select_n"
                          for sub in jax.core.jaxprs_in_params(e.params)
                          for i in walk(sub)) for e in loops]
            assert sorted(masked) == [False, True]

    @pytest.mark.parametrize("args, want", [
        ((8192, 512, 512, True, 2048),
         "blocks 512x512, pairs a head: window 2048: 42 full, 16 on the "
         "diagonal, 12 on the window's edge, 186 skipped; a diagonal pair "
         "as 2x2 of 256: 1 full, 2 masked, 1 skipped"),
        ((1024, 256, 256, True, 100),
         "blocks 256x256, pairs a head: window 100: 0 full, 4 on the "
         "diagonal, 3 on the window's edge, 9 skipped; a diagonal pair as "
         "2x2 of 128: 0 full, 3 masked, 1 skipped"),
        ((1024, 128, 128, True, 200),
         "blocks 128x128, pairs a head: window 200: 0 full, 8 on the "
         "diagonal, 13 on the window's edge, 43 skipped, masked whole"),
    ], ids=lambda a: "-".join(map(str, a)) if isinstance(a, tuple) else "")
    def test_block_pairs_line_counts_the_band(self, args, want):
        from edl_tpu.ops.flash_attention import block_pairs
        assert block_pairs(*args) == want

    def test_a_window_needs_causal_and_a_key(self):
        q, k, v = _qkv(b=1, s=128, h=1)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, causal=False, window=64)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, window=0)


def _masked_dense(q, k, v, *, window=None, blocks=None):
    """The oracle at any mask the kernels know: a dense masked softmax
    in float32, the scale from the key size."""
    s = q.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    if blocks is not None:
        size, strict = blocks
        seen = j // size < i // size if strict else j // size <= i // size
    else:
        seen = j <= i if window is None else (j <= i) & (i - j < window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class TestValueHeadSize:
    """v with a head size of its own (latent attention: keys of 192,
    values of 128): q, k (B, S, H, D), v, o, dO, dV (B, S, H, Dv), the
    scale from D. Both dispatch modes against the dense oracle."""

    # (D, Dv): keys wider than values at the cell's ratio and at its
    # sizes, values wider than keys, and one size on neither's lanes
    SIZES = [(24, 16), (192, 128), (16, 32), (40, 24)]
    MASKS = [{}, {"window": 100}, {"blocks": (4, False)},
             {"blocks": (4, True)}]

    @staticmethod
    def _qkv(d, dv, s=256, h=2, dtype=jnp.float32):
        q, k, _ = _qkv(b=1, s=s, h=h, d=d, dtype=dtype)
        return q, k, _qkv(b=1, s=s, h=h, d=dv, dtype=dtype, seed=1)[2]

    @pytest.mark.parametrize("mask", MASKS, ids=lambda m: "-".join(
        f"{k}{v}" for k, v in m.items()) or "causal")
    @pytest.mark.parametrize("d, dv", SIZES)
    def test_forward_and_backward_match_dense(self, d, dv, mask, attn_path):
        q, k, v = self._qkv(d, dv)
        kw = dict(block_q=128, block_k=128, **mask)
        # a strict query of the first block sees no key: what it gets is
        # the kernels' own convention (tests/test_sdar.py), left out here
        first = 4 if mask.get("blocks", (0, False))[1] else 0
        out = flash_attention(q, k, v, **kw)
        assert out.shape == v.shape
        np.testing.assert_allclose(
            out[:, first:], _masked_dense(q, k, v, **mask)[:, first:],
            atol=2e-5)

        def loss(fn, **kw):
            return lambda q, k, v: jnp.sum(jnp.sin(
                fn(q, k, v, **kw)[:, first:]))
        got = jax.grad(loss(flash_attention, **kw), argnums=(0, 1, 2))(
            q, k, v)
        want = jax.grad(loss(_masked_dense, **mask), argnums=(0, 1, 2))(
            q, k, v)
        for g, w, x in zip(got, want, (q, k, v)):
            assert g.shape == x.shape
            np.testing.assert_allclose(g, w, atol=1e-4)

    @pytest.mark.parametrize("blk_q, blk_k", [(256, 256), (128, 256),
                                              (512, 512)])
    def test_sub_blocks_and_unequal_blocks(self, blk_q, blk_k, attn_path):
        q, k, v = self._qkv(24, 16, s=512)
        out = flash_attention(q, k, v, block_q=blk_q, block_k=blk_k)
        np.testing.assert_allclose(out, dense_attention(q, k, v), atol=2e-5)

    def test_dense_attention_takes_it_too(self):
        q, k, v = self._qkv(24, 16)
        np.testing.assert_allclose(dense_attention(q, k, v),
                                   _masked_dense(q, k, v), atol=2e-5)
        np.testing.assert_allclose(
            dense_attention(q, k, v, window=100),
            _masked_dense(q, k, v, window=100), atol=2e-5)

    def test_the_scale_is_from_the_key_size(self, attn_path):
        q, k, v = self._qkv(24, 16)
        np.testing.assert_allclose(
            flash_attention(q, k, v),
            flash_attention(q, k, v, scale=24 ** -0.5), atol=1e-6)
        assert float(jnp.max(jnp.abs(
            flash_attention(q, k, v)
            - flash_attention(q, k, v, scale=16 ** -0.5)))) > 1e-3

    def test_lse_and_its_cotangent(self, attn_path):
        from edl_tpu.ops.flash_attention import flash_attention_lse
        q, k, v = self._qkv(24, 16)

        def dense(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bqhk", q, k) / 24 ** 0.5
            seen = jnp.arange(256)[:, None] >= jnp.arange(256)[None, :]
            return jax.nn.logsumexp(
                jnp.where(seen[None, :, None, :], s, -1e30), axis=-1)

        def through(q, k, v):
            o, lse = flash_attention_lse(q, k, v)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))

        def oracle(q, k, v):
            return jnp.sum(jnp.sin(_masked_dense(q, k, v))) + jnp.sum(
                jnp.cos(dense(q, k, v)))
        np.testing.assert_allclose(flash_attention_lse(q, k, v)[1],
                                   dense(q, k, v), atol=2e-5)
        for g, w in zip(jax.grad(through, argnums=(0, 1, 2))(q, k, v),
                        jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(g, w, atol=1e-4)

    def test_bfloat16_at_the_cells_sizes(self, attn_path):
        q, k, v = self._qkv(192, 128, s=512, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v)
        assert out.dtype == jnp.bfloat16 and out.shape == v.shape
        want = _masked_dense(*(x.astype(jnp.float32) for x in (q, k, v)))
        np.testing.assert_allclose(out.astype(jnp.float32), want, atol=3e-2)

    @pytest.mark.parametrize("bad", ["k", "v_rows", "v_rank"])
    def test_shapes_that_are_not_legal_are_named(self, bad):
        q, k, v = self._qkv(24, 16)
        if bad == "k":
            k = k[..., :16]
        elif bad == "v_rows":
            v = v[:, :128]
        else:
            v = v[..., 0]
        with pytest.raises(ValueError, match=r"v is \(B, S, H, Dv\)"):
            flash_attention(q, k, v)

    # sha256[:16] of str(jax.make_jaxpr(...)) of the forward and the
    # backward at Dv == D, taken on the tree before v had a size of its
    # own (commit 92c3a63): the Mosaic kernels (interpret=True changes
    # no equation of the body) and the XLA blockwise pair. A JAX that
    # prints jaxprs differently needs them taken again from that commit.
    PINS = {
        "causal": ((1, 512, 2, 64, jnp.float32), {},
                   "523a0d8d7bd4ef85", "53e517beecb4a4f2"),
        "window": ((1, 512, 2, 128, jnp.bfloat16), {"window": 200},
                   "3608f08a42b99d7b", "5432af9ba5184d92"),
        "blocks": ((1, 512, 2, 128, jnp.bfloat16), {"blocks": (4, True)},
                   "624d2a0c916a8928", "7227247b27a8cadd"),
    }

    @pytest.mark.parametrize("path", ["pallas_kernels", "xla_blockwise"])
    @pytest.mark.parametrize("case", sorted(PINS))
    def test_equal_head_sizes_lower_to_the_jaxpr_they_did(self, case, path):
        import hashlib

        from edl_tpu.ops.flash_attention import (_bwd_blockwise,
                                                 _bwd_pallas, _fwd,
                                                 _fwd_blockwise)
        (b, s, h, d, dtype), mask, kernels, blockwise = self.PINS[case]
        q, k, v = _qkv(b=b, s=s, h=h, d=d, dtype=dtype)
        if path == "pallas_kernels":
            kw = dict(blk_q=256, blk_k=256, scale=0.1, causal=True,
                      interpret=True, **mask)

            def both(q, k, v):
                o, lse = _fwd(q, k, v, **kw)
                return _bwd_pallas(q, k, v, o, lse, q, dlse=None, **kw)
        else:
            kw = dict(blk=256, scale=0.1, causal=True, **mask)

            def both(q, k, v):
                o, lse = _fwd_blockwise(q, k, v, **kw)
                return _bwd_blockwise(q, k, v, o, lse, q, **kw)
        text = str(jax.make_jaxpr(both)(q, k, v))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
            kernels if path == "pallas_kernels" else blockwise)
