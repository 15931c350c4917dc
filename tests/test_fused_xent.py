"""One-sweep cross-entropy (ops/fused_xent.py) vs the dense oracle."""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.ops.fused_xent import blocking, describe, streamed_lm_xent


def _data(b=6, s=32, d=32, v=512, seed=0):
    """The last position of every sequence does not count, as in an LM
    batch: 6 x 31 rows."""
    key = jax.random.PRNGKey(seed)
    h = jax.random.normal(key, (b, s, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) * 0.1
    t = jax.random.randint(jax.random.fold_in(key, 2), (b, s), 0, v)
    return h, k, t.at[:, -1].set(-1)


def _oracle(h, k, t):
    logp = jax.nn.log_softmax(h.astype(jnp.float32) @ k)
    ll = jnp.take_along_axis(logp, jnp.maximum(t, 0)[..., None], -1)[..., 0]
    return -jnp.sum(jnp.where(t >= 0, ll, 0.0)) / jnp.sum(t >= 0)


def _grads(h, k, t, block_rows):
    return jax.grad(lambda h, k: streamed_lm_xent(h, k, t, block_rows),
                    argnums=(0, 1))(h, k)


class TestStreamedXent:
    @pytest.mark.parametrize("block_rows", [None, 48, 96, 192])
    def test_loss_matches_oracle(self, block_rows):
        """One block (None: 6 x 32 rows x 512 fit), 4, 2 and 1 blocks."""
        h, k, t = _data()
        np.testing.assert_allclose(
            float(streamed_lm_xent(h, k, t, block_rows)),
            float(_oracle(h, k, t)), atol=2e-6)

    @pytest.mark.parametrize("b,s,block_rows", [
        (6, 32, None),  # one block
        (6, 32, 48),    # four full blocks
        (6, 32, 40),    # 6 x 31 rows that count; 5 blocks reach 200 of 192
        (4, 64, 100),   # 4 x 63 rows that count; 3 blocks reach 300 of 256
    ])
    def test_grads_match_oracle(self, b, s, block_rows):
        h, k, t = _data(b=b, s=s)
        blocks, rows = blocking(b * s, 512, block_rows)
        assert blocks * rows >= b * s > (blocks - 1) * rows
        go = jax.grad(_oracle, argnums=(0, 1))(h, k, t)
        gf = _grads(h, k, t, block_rows)
        np.testing.assert_allclose(gf[0], go[0], atol=1e-6)
        np.testing.assert_allclose(gf[1], go[1], atol=1e-6)
        # a row that does not count moves nothing
        assert not np.asarray(gf[0][:, -1]).any()

    def test_extreme_logits_stable(self):
        """The row max must carry large-magnitude logits."""
        h, k, t = _data()
        k = k * 100.0
        got = float(streamed_lm_xent(h, k, t, 48))
        want = float(_oracle(h, k, t))
        assert np.isfinite(got)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_bf16_hidden_f32_kernel(self):
        """The trainer's dtypes: gradients come back in their operand's."""
        h, k, t = _data()
        hb = h.astype(jnp.bfloat16)
        np.testing.assert_allclose(float(streamed_lm_xent(hb, k, t, 48)),
                                   float(_oracle(hb, k, t)), atol=1e-5)
        go = jax.grad(_oracle, argnums=(0, 1))(hb, k, t)
        gf = _grads(hb, k, t, 48)
        assert gf[0].dtype == jnp.bfloat16 and gf[1].dtype == jnp.float32
        np.testing.assert_allclose(gf[0].astype(jnp.float32),
                                   go[0].astype(jnp.float32), atol=1e-4)
        np.testing.assert_allclose(gf[1], go[1], atol=1e-6)

    @pytest.mark.parametrize("v,block_rows", [(50257 % 997 + 500, 48),
                                              (1000, 100), (513, None)])
    def test_ragged_vocab_matches_oracle(self, v, block_rows):
        """A vocabulary that is no multiple of 128: every block sees all
        of it, so there is nothing to clamp or mask."""
        h, k, t = _data(v=v)
        np.testing.assert_allclose(
            float(streamed_lm_xent(h, k, t, block_rows)),
            float(_oracle(h, k, t)), atol=2e-6)
        go = jax.grad(_oracle, argnums=(0, 1))(h, k, t)
        gf = _grads(h, k, t, block_rows)
        np.testing.assert_allclose(gf[0], go[0], atol=1e-6)
        np.testing.assert_allclose(gf[1], go[1], atol=1e-6)

    def test_scaled_cotangent(self):
        """A cotangent other than 1: the backward rule multiplies the
        kept gradients by it."""
        h, k, t = _data()
        scale = 32768.0
        g = jax.grad(lambda h, k: scale * streamed_lm_xent(h, k, t, 48),
                     argnums=(0, 1))(h, k)
        go = jax.grad(_oracle, argnums=(0, 1))(h, k, t)
        np.testing.assert_allclose(g[0] / scale, go[0], atol=1e-6)
        np.testing.assert_allclose(g[1] / scale, go[1], atol=1e-6)
        g3 = jax.grad(lambda h: 3.0 * streamed_lm_xent(h, k, t, 48))(h)
        np.testing.assert_allclose(g3, 3.0 * go[0], atol=3e-6)

    def test_n_rows_of_a_larger_batch(self):
        """A caller that holds a share of the batch gives the whole
        batch's count: the shares' sums add up to the whole's mean."""
        h, k, t = _data()
        n = jnp.sum(t >= 0)
        parts = [streamed_lm_xent(h[i:i + 3], k, t[i:i + 3], 48, n)
                 for i in (0, 3)]
        np.testing.assert_allclose(float(parts[0] + parts[1]),
                                   float(_oracle(h, k, t)), atol=2e-6)

    def test_three_matmuls_not_four(self):
        """The gradient is made in the sweep that makes the loss: three
        N x d x V products under value_and_grad (logits, d_hidden,
        d_kernel), one without a gradient, and no replay."""
        h, k, t = _data()
        both = jax.make_jaxpr(jax.value_and_grad(
            lambda h, k: streamed_lm_xent(h, k, t, 48), argnums=(0, 1)))(h, k)
        assert str(both).count("dot_general") == 3
        primal = jax.make_jaxpr(
            lambda h, k: streamed_lm_xent(h, k, t, 48))(h, k)
        assert str(primal).count("dot_general") == 1
        assert str(both).count(" scan[") == 1  # one loop

    def test_blocking(self):
        """From the shapes alone; the cells' own first."""
        assert blocking(6 * 2048, 50257) == (8, 1536)  # lm_d8.steady
        assert blocking(4 * 4096, 50304) == (11, 1536)  # olmoe_d1.steady
        assert blocking(2 * 2048, 50257) == (3, 1536)  # a chip's rows, fsdp
        assert blocking(6 * 32, 512) == (1, 192)       # fits one block
        assert blocking(12282, 50257) == (8, 1536)     # the last not full
        assert blocking(1 << 20, 1 << 15) == (410, 2560)
        assert blocking(1 << 20, 1 << 22) == (4096, 256)  # never under 256
        assert blocking(6 * 32, 512, 40) == (5, 40)    # tests name the rows
        assert describe(6 * 2048, 50257) == (
            "one sweep, 8 blocks of 1536 rows x 50257, 3 matmuls")

    def test_jits(self):
        h, k, t = _data()
        f = jax.jit(lambda h, k, t: streamed_lm_xent(h, k, t, 48))
        assert np.isfinite(float(f(h, k, t)))


def _state_and_batch(mesh=None, batch=4, vocab=512):
    from edl_tpu.models.transformer import Transformer, TransformerConfig
    from edl_tpu.parallel import sharding as shd
    from edl_tpu.train.state import TrainState

    cfg = TransformerConfig(vocab_size=vocab, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_len=64,
                            dtype=jnp.float32, mesh=mesh)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, 64), 0, vocab)

    def init():
        return model.init(jax.random.PRNGKey(0), toks[:1], train=False)

    params = (flax.linen.meta.unbox(init()) if mesh is None
              else shd.init_sharded(init, mesh))["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.sgd(0.1))
    return state, toks


class TestFusedLmLoss:
    def test_matches_dense_loss_and_grads(self):
        from edl_tpu.models.transformer import lm_loss_fn, lm_loss_fused

        state, toks = _state_and_batch()
        batch = {"tokens": toks}
        l1, _ = lm_loss_fn(state, state.params, batch)
        l2, _ = lm_loss_fused(state, state.params, batch, block_rows=96)
        np.testing.assert_allclose(float(l1), float(l2), atol=5e-6)
        g1 = jax.grad(lambda p: lm_loss_fn(state, p, batch)[0])(state.params)
        g2 = jax.grad(lambda p: lm_loss_fused(
            state, p, batch, block_rows=96)[0])(state.params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(a, b, atol=2e-6)

    @pytest.mark.parametrize("axis", ["fsdp", "dp"])
    def test_sharded_batch_matches_one_device(self, axis):
        """The d8-shaped tiny model under a 4-way mesh: each chip sweeps
        its own two sequences. Loss and gradients equal the unsharded
        ones; the head kernel is gathered once (fsdp shards it) and its
        gradient reduced once, outside the sweep's loop, and no
        collective makes the hidden rows whole again."""
        import re

        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from edl_tpu.models.transformer import lm_loss_fused

        vocab = 50257 % 997 + 500
        one, toks = _state_and_batch(batch=8, vocab=vocab)
        mesh = Mesh(np.array(jax.devices()[:4]), (axis,))
        four, _ = _state_and_batch(mesh, batch=8, vocab=vocab)
        assert four.apply_fn.__self__.cfg.xent_shards() == 4
        assert one.apply_fn.__self__.cfg.xent_shards() == 1
        kspec = P("fsdp") if axis == "fsdp" else P()
        assert four.params["lm_head"]["kernel"].sharding.spec == kspec
        sharded = jax.device_put(toks, NamedSharding(mesh, P(axis)))

        def loss_and_grads(state):
            return jax.jit(jax.value_and_grad(lambda p, tokens: lm_loss_fused(
                state, p, {"tokens": tokens}, block_rows=64)[0]))

        l1, g1 = loss_and_grads(one)(one.params, toks)
        fn = loss_and_grads(four)
        l4, g4 = fn(four.params, sharded)
        np.testing.assert_allclose(float(l1), float(l4), atol=2e-6)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
            np.testing.assert_allclose(a, b, atol=2e-6)

        text = fn.lower(four.params, sharded).compile().as_text()
        # the sweep is the program's one loop (two blocks of 64 rows a
        # chip): no collective inside it
        assert len(re.findall(r" while\(", text)) == 1
        body = re.search(r" while\(.*body=%?([\w.\-]+)", text).group(1)
        body = re.search(r"^%?" + re.escape(body) + r" \(.*\{\n(.*\n)*?\}",
                         text, re.M).group(0)
        assert " dot(" in body or " fusion(" in body
        assert not re.search(r"(all-gather|all-reduce|reduce-scatter|"
                             r"all-to-all|collective-permute)", body)
        # outside it: the kernel gathered once and its gradient reduced
        # once (fsdp), and no gather of 8 sequences or 8 x 64 rows
        gathers = [line.split("=", 1)[1] for line in text.splitlines()
                   if re.search(r" all-gather(-start)?\(", line)]
        if axis == "fsdp":
            assert sum(f"[64,{vocab}]" in g.split("(")[0]
                       for g in gathers) == 1
            scatters = [line for line in text.splitlines()
                        if re.search(r" reduce-scatter(-start)?\(", line)]
            assert len(scatters) == 1
            # both under the op's scope, for the trace's readers
            assert "(xent))/shard_map/" in scatters[0]
            assert any(f"[64,{vocab}]" in g.split("(")[0]
                       and "(xent)/shard_map/" in g for g in gathers)
        for line in gathers:
            dims = re.search(r"\[([\d,]*)\]", line).group(1)
            dims = [int(x) for x in dims.split(",") if x]
            assert dims[0] != 8 and 8 * 64 not in dims, line

    @pytest.mark.slow
    def test_lm_train_fused_flag(self, tmp_path):
        from edl_tpu.examples.lm_train import main

        rc = main(["--data-dir", str(tmp_path / "d"), "--make-synthetic",
                   "1", "--rows-per-file", "128", "--vocab", "128",
                   "--seq-len", "32", "--d-model", "32", "--n-heads", "2",
                   "--n-layers", "1", "--d-ff", "64", "--epochs", "1",
                   "--batch-size", "16", "--fused-loss"])
        assert rc == 0
