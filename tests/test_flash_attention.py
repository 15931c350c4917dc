"""Pallas flash attention vs the dense oracle.

Off-TPU the public API dispatches to compiled XLA blockwise paths, so
every dense-parity test here runs under BOTH dispatch modes via the
`attn_path` fixture: the XLA fallback, and the Pallas kernels forced
through the same custom_vjp path in interpret mode (interpret=True
executes the same kernel body) — block logic, causal skip,
online-softmax accumulation, and both backwards stay covered on CPU.
"""

import contextlib
import functools

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
        return _walk(jaxpr)

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
            "flash (1, 512, 1, 64) kv 1: a key/value head by its index, "
            "one to 1 query heads",
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


class TestNoRepeatInTheBlocks:
    """The grouped-query blocks hand the flash kernels their key/value
    heads as projected: traced at the cells' head counts (a group of 8,
    the hybrid's 4 at a head of 64) and a small sequence, an attention
    layer's forward and backward hold no broadcast of k or v over a
    group (how `jnp.repeat` lowers) and every flash call takes B * KV
    key/value heads. The dense path, which is XLA's, keeps the repeat."""

    @staticmethod
    def _layer(name, attention):
        from edl_tpu.models import transformer as tfm
        small = dict(vocab_size=64, d_model=256, n_layers=1, d_ff=64,
                     max_len=512, dtype=jnp.float32, attention=attention)
        if name == "sdar":
            return tfm.Attention(tfm.sdar_config(
                n_heads=8, n_kv_heads=1, n_experts=2, moe_top_k=1,
                **small)), 1
        if name == "hybrid":  # one attention layer's shape: head of 64
            return tfm.Attention(tfm.TransformerConfig(
                n_heads=4, n_kv_heads=1, **small)), 1
        kind = name.split("-")[1]
        return tfm.Attention(tfm.afmoe_config(
            n_heads=8, n_kv_heads=1, window=128, n_experts=2, moe_top_k=1,
            **small), kind), 1

    @classmethod
    def _eqns(cls, name, attention):
        from edl_tpu.ops import rope
        layer, kv = cls._layer(name, attention)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 512, 256))
        params = layer.init(jax.random.PRNGKey(1), x)

        def loss(params, x):
            return jnp.sum(jnp.sin(layer.apply(params, x)))
        with force_interpret_kernels(), rope.force_interpret_kernel():
            jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
        eqns = list(_walk(jaxpr.jaxpr, into_kernels=False))
        # a (B, S, KV, D) made (B, S, KV, group, D): heads' whole rows
        over_groups = [e for e in eqns
                       if e.primitive.name == "broadcast_in_dim"
                       and e.invars[0].aval.ndim >= 4
                       and e.outvars[0].aval.ndim == 5
                       and e.outvars[0].aval.shape[-1] in (64, 128)
                       and e.outvars[0].aval.size > e.invars[0].aval.size]
        flash = [e for e in eqns if e.primitive.name == "pallas_call"
                 and e.params["name"].startswith("flash_")]
        return over_groups, flash, x.shape[0] * kv

    @pytest.mark.parametrize("name", ["trinity-sliding", "trinity-full",
                                      "sdar", "hybrid"])
    def test_the_flash_path_repeats_nothing(self, name):
        over_groups, flash, heads = self._eqns(name, "flash")
        assert not over_groups
        # sdar: the clean copy's call and the noised queries', each
        # forward, replayed by nothing here, and backward
        assert len(flash) == (6 if name == "sdar" else 3)
        for e in flash:
            assert [x.aval.shape[0] for x in e.invars[1:3]] == [heads, heads]

    def test_the_dense_path_keeps_its_repeat(self):
        over_groups, flash, _ = self._eqns("trinity-sliding", "dense")
        assert len(over_groups) >= 2 and not flash


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


def _walk(jaxpr, into_kernels=True):
    """Every equation of a jaxpr and of all it holds."""
    for e in jaxpr.eqns:
        yield e
        if into_kernels or e.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from _walk(sub, into_kernels)


class TestGroupedHeads:
    """Grouped-query attention inside the kernels: k and v come with
    their own KV heads, a query head reads key/value head h // (H / KV)
    by index, and dK, dV come back summed over each group. Nothing is
    written once a query head on the way in."""

    # (batch, query heads, key/value heads, D, Dv, mask)
    CASES = {
        "equal": (2, 2, 2, 128, 128, {}),
        "group8": (1, 8, 1, 128, 128, {}),
        "group4": (2, 8, 2, 128, 128, {}),
        "window": (1, 4, 1, 128, 128, {"window": 100}),
        "blocks-own": (1, 4, 1, 128, 128, {"blocks": (4, False)}),
        "blocks-before": (1, 8, 2, 128, 128, {"blocks": (4, True)}),
        "values-of-their-own": (1, 4, 2, 256, 128, {}),
        "head64": (2, 4, 1, 64, 64, {}),
        "head64-window": (1, 8, 2, 64, 64, {"window": 100}),
        "head192": (1, 4, 2, 192, 128, {}),
    }
    GROUPED = sorted(c for c in CASES if c != "equal")
    S, BLK = 256, 128

    @classmethod
    def _operands(cls, case, dtype=jnp.float32):
        b, h, kv, d, dv, mask = cls.CASES[case]
        key = jax.random.PRNGKey(sum(map(ord, case)))
        shapes = [(h, d), (kv, d), (kv, dv), (h, dv)]
        q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i),
                                         (b, cls.S, *x), dtype)
                       for i, x in enumerate(shapes))
        dlse = jax.random.normal(jax.random.fold_in(key, 4),
                                 (b * h, cls.S), jnp.float32)
        # a strict query of the first block sees no key: what it gets is
        # the kernels' own convention (tests/test_sdar.py), left out here
        first = 4 if mask.get("blocks", (0, False))[1] else 0
        return (q, k, v, do.at[:, :first].set(0),
                dlse.at[:, :first].set(0)), mask, first

    @classmethod
    def _kernels(cls, q, k, v, do, dlse, mask):
        """(o, lse, dq, dk, dv) of the three kernels, interpret mode."""
        from edl_tpu.ops.flash_attention import _bwd_pallas, _fwd
        kw = dict(blk_q=cls.BLK, blk_k=cls.BLK, scale=q.shape[-1] ** -0.5,
                  causal=True, interpret=True, **mask)
        o, lse = _fwd(q, k, v, **kw)
        return (o, lse) + _bwd_pallas(q, k, v, o, lse, do, dlse=dlse, **kw)

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_kernels_match_the_blockwise_scans(self, case):
        """Forward, lse and all of dq, dk, dv under a non-zero `dlse`,
        against `_fwd_blockwise` / `_bwd_blockwise`, which repeat the
        heads inside themselves."""
        from edl_tpu.ops.flash_attention import (_bwd_blockwise,
                                                 _fwd_blockwise)
        (q, k, v, do, dlse), mask, first = self._operands(case)
        kw = dict(blk=self.BLK, scale=q.shape[-1] ** -0.5, causal=True,
                  **mask)
        o, lse, *grads = self._kernels(q, k, v, do, dlse, mask)
        o_x, lse_x = _fwd_blockwise(q, k, v, **kw)
        assert o.shape == do.shape and lse.shape == dlse.shape
        np.testing.assert_allclose(o[:, first:], o_x[:, first:], atol=2e-5)
        np.testing.assert_allclose(lse[:, first:], lse_x[:, first:],
                                   atol=2e-5)
        want = _bwd_blockwise(q, k, v, o_x, lse_x, do, dlse=dlse, **kw)
        for g, w, x in zip(grads, want, (q, k, v)):
            assert g.shape == x.shape
            np.testing.assert_allclose(g, w, atol=1e-4)

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_both_paths_match_dense_attention_on_repeated_heads(
            self, case, attn_path):
        """The public function against the dense oracle handed every
        key/value head once a query head, whose autodiff sums dK, dV."""
        (q, k, v, _, _), mask, first = self._operands(case)
        group = q.shape[2] // k.shape[2]

        def oracle(q, k, v):
            return _masked_dense(q, jnp.repeat(k, group, 2),
                                 jnp.repeat(v, group, 2), **mask)

        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)[:, first:]))
        kw = dict(block_q=self.BLK, block_k=self.BLK, **mask)
        np.testing.assert_allclose(
            flash_attention(q, k, v, **kw)[:, first:],
            oracle(q, k, v)[:, first:], atol=2e-5)
        got = jax.grad(loss(functools.partial(flash_attention, **kw)),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, atol=1e-4)

    @pytest.mark.parametrize("case", GROUPED)
    def test_a_head_by_index_is_the_repeated_head_to_the_bit(self, case):
        """The same kernels on the same numbers, bfloat16 operands: o,
        lse and dq equal what repeated heads give, and dK, dV are the
        sums of what each query head of a group gave."""
        (q, k, v, do, dlse), mask, _ = self._operands(case, jnp.bfloat16)
        b, s, h, kv = *q.shape[:3], k.shape[2]
        by_index = self._kernels(q, k, v, do, dlse, mask)
        repeated = self._kernels(q, jnp.repeat(k, h // kv, 2),
                                 jnp.repeat(v, h // kv, 2), do, dlse, mask)
        for got, want in zip(by_index[:3], repeated[:3]):
            np.testing.assert_array_equal(got.astype(jnp.float32),
                                          want.astype(jnp.float32))
        for got, want in zip(by_index[3:], repeated[3:]):
            assert got.shape[2] == kv and got.dtype == jnp.bfloat16
            heads = want.astype(jnp.float32).reshape(b, s, kv, h // kv, -1)
            # each query head's part was rounded to bfloat16 there; here
            # the sum is float32 until it is written
            np.testing.assert_allclose(
                got.astype(jnp.float32), heads.sum(3), rtol=2 ** -8,
                atol=(h // kv) * 2 ** -9 * float(jnp.max(jnp.abs(heads))))

    @pytest.mark.parametrize("case", GROUPED)
    def test_the_sum_over_a_group_in_slabs_is_the_kernels_own(
            self, case, monkeypatch):
        """Where a whole sequence does not fit the kernel's VMEM, dK
        and dV leave it a query head, in slabs, and XLA sums them: the
        same gradients as the kernel that sums over the group itself."""
        import importlib
        fa = importlib.import_module("edl_tpu.ops.flash_attention")
        (q, k, v, do, dlse), mask, _ = self._operands(case)
        kw = dict(blk_q=self.BLK, blk_k=self.BLK, scale=0.1, causal=True,
                  interpret=True, dlse=dlse, **mask)
        o, lse = fa._fwd(q, k, v, **{x: kw[x] for x in kw if x != "dlse"})

        def backward(*a):  # untraced each time: the rule is asked again
            return fa._bwd_pallas.__wrapped__(*a, o, lse, do, **kw)

        def sums(fn):  # a new function each time: traces are kept
            return [e.primitive.name for e in _walk(
                jax.make_jaxpr(lambda *a: fn(*a))(q, k, v).jaxpr,
                into_kernels=False)
                if e.primitive.name == "reduce_sum"
                and e.invars[0].aval.ndim == 4]
        in_kernel = backward(q, k, v)
        assert not sums(backward)
        monkeypatch.setattr(fa, "_GROUP_VMEM", 0)
        assert len(sums(backward)) == 2
        for got, want in zip(backward(q, k, v), in_kernel):
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want, atol=1e-5)

    @pytest.mark.parametrize("shape, fits", [
        ((8192, 128, 128, 2), True),      # the afmoe and diffusion cells
        ((8192, 64, 64, 2), True),        # the hybrid's layer: 128 lanes
        ((16384, 128, 128, 2), False),
        ((8192, 192, 128, 2), False),
        ((4096, 128, 128, 4), True),
    ], ids=str)
    def test_where_the_kernel_sums_is_a_matter_of_vmem(self, shape, fits):
        from edl_tpu.ops.flash_attention import _Heads
        assert _Heads(32, 8).sums_in_kernel(*shape) is fits
        assert not _Heads(32, 1).sums_in_kernel(*shape)

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_the_trace_says_how_many_heads_a_key_serves(self, case,
                                                        caplog):
        import logging
        (q, k, v, _, _), mask, _ = self._operands(case)
        b, h, kv, d, _, _ = self.CASES[case]
        fa_log = logging.getLogger("edl_tpu.ops.flash_attention")
        fa_log.addHandler(caplog.handler)
        try:
            with force_interpret_kernels():
                jax.eval_shape(functools.partial(
                    flash_attention, block_q=self.BLK, block_k=self.BLK,
                    **mask), q, k, v)
        finally:
            fa_log.removeHandler(caplog.handler)
        assert (f"flash ({b}, {self.S}, {h}, {d}) kv {kv}: a key/value "
                f"head by its index, one to {h // kv} query heads"
                in [r.getMessage() for r in caplog.records])

    @pytest.mark.parametrize("case", GROUPED)
    def test_no_key_or_value_is_written_once_a_query_head(self, case):
        """The traced forward and backward around the three kernels:
        the kernels are handed B * KV key/value heads, and nothing of
        a whole (B, S, H, D) is made from k or v (no broadcast over a
        group, which is how `jnp.repeat` lowers; no gather)."""
        (q, k, v, do, dlse), mask, _ = self._operands(case)
        b, s, h, kv = *q.shape[:3], k.shape[2]
        eqns = list(_walk(jax.make_jaxpr(
            lambda *a: self._kernels(*a, mask))(q, k, v, do, dlse).jaxpr,
            into_kernels=False))
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == [
            "flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"]
        for e in calls:
            assert [x.aval.shape[0] for x in e.invars[:3]] == [
                b * h, b * kv, b * kv]
        assert not [e for e in eqns if e.primitive.name in (
            "broadcast_in_dim", "gather", "concatenate")
            and e.outvars[0].aval.ndim >= 4]

    @pytest.mark.parametrize("bad", ["kv_heads", "v_heads", "k_size"])
    def test_heads_that_do_not_group_are_refused(self, bad):
        (q, k, v, _, _), _, _ = self._operands("group4")
        if bad == "kv_heads":  # 8 query heads on 3
            k, v = (jnp.concatenate([x, x[:, :, :1]], 2) for x in (k, v))
        elif bad == "v_heads":
            v = v[:, :, :1]
        else:
            k = k[..., :64]
        with pytest.raises(ValueError, match="KV divides H"):
            flash_attention(q, k, v)


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
        with pytest.raises(ValueError, match=r"v is \(B, S, KV, Dv\)"):
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
