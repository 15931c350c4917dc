"""Fused optimizer kernels + quantized resident moments.

The contracts under test (ops/opt_kernels.py + train/fused_opt.py):

- the interpret-mode Pallas kernel is BITWISE-identical to the
  plain-XLA fallback for every optimizer x quant mode (the structural
  guarantee the TPU path inherits), and fused-fp32 momentum-SGD is
  bitwise vs the optax chain;
- error feedback conserves update mass: the quantized two-plane moment
  reconstructs to within the second-order bound, and residuals carry
  across steps so the quantized trajectory tracks the fp32 one;
- quantized (q, scale) moment leaves round-trip BITWISE through the
  replicated checkpoint, the sharded checkpoint across mesh shapes
  (4 -> 2 and 4 -> 8 devices) and the peer-migration wire;
- the fused step donates every state buffer (params AND quantized
  planes alias in place — the raw-speed point of the exercise);
- the knobs route: --fused-opt modes map to the right tx, env vars
  reach LoopConfig, invalid combos raise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from edl_tpu.ops import opt_kernels as ok
from edl_tpu.train import comm as comm_lib
from edl_tpu.train import fused_opt as fo
from edl_tpu.train import sharded_checkpoint as sc
from edl_tpu.train.checkpoint import CheckpointManager
from edl_tpu.train.state import TrainState, TrainStatus
from edl_tpu.train.step import donation_coverage, make_train_step

QUANTS = ["int8"] + (["fp8"] if ok.fp8_dtype() else [])


def host_tree(t):
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), t)


def assert_trees_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        np.testing.assert_array_equal(x, y)


# -- kernel == XLA equivalence (the parity gate CI also runs) ---------------


class TestKernelEquivalence:
    def test_parity_gate_green(self):
        report = fo.update_parity_gate(steps=2)
        # named asserts so a regression says WHICH leg broke
        assert report["sgdm_fp32_vs_optax_bitwise"]
        assert report["adam_fp32_vs_optax_close"], \
            report["adam_fp32_vs_optax_max_err"]
        for q in ["off"] + QUANTS:
            assert report[f"sgdm_{q}_kernel_bitwise"], q
            assert report[f"adam_{q}_kernel_bitwise"], q
        assert report["ok"]

    def test_schedule_feeds_from_step_count(self):
        """A callable learning rate sees count 0, 1, ... (the
        scale_by_schedule convention optax trains with)."""
        params, grads = fo._gate_world(3)
        seen = []

        def sched(count):
            seen.append(count)
            return 0.1

        tx = fo.fused_sgd(sched, 0.9, bucket_mb=0.05)
        state = tx.init(params)
        for _ in range(3):
            params, state = tx.fused_apply(grads, state, params)
        assert [int(c) for c in seen] == [0, 1, 2]
        assert int(state.count) == 3


# -- error feedback ----------------------------------------------------------


class TestErrorFeedback:
    @pytest.mark.parametrize("quant", QUANTS)
    def test_two_plane_reconstruction_bound(self, quant):
        """payload + residual behaves like ~16-bit fixed precision:
        the reconstruction error is second-order (residual-plane
        rounding), far below a single int8 plane's."""
        rng = np.random.default_rng(0)
        m = jnp.asarray(rng.normal(0, 0.05, size=(4096,))
                        .astype(np.float32))
        plane = ok.quant_plane(m, quant)
        recon = ok.dequant_plane(plane, quant)
        err2 = float(jnp.max(jnp.abs(m - recon)))
        one_plane = (ok.dequantize_int8(plane.q, plane.scale)
                     if quant == "int8"
                     else ok._dequantize_fp8(plane.q, plane.scale))
        err1 = float(jnp.max(jnp.abs(m - one_plane)))
        if quant == "int8":
            assert err2 < err1 / 50
            assert err2 <= float(plane.scale) / 254  # second-order bound
        else:
            # e4m3 keeps ~6% relative precision: the residual plane
            # still buys an order of magnitude, not int8's two
            assert err2 < err1 / 10

    def test_zero_plane_is_exact(self):
        for quant in QUANTS:
            plane = ok.zero_plane(256, quant)
            np.testing.assert_array_equal(
                np.asarray(ok.dequant_plane(plane, quant)),
                np.zeros(256, np.float32))

    @pytest.mark.parametrize("quant", QUANTS)
    def test_residual_carryover_tracks_fp32_moments(self, quant):
        """Across steps the residual re-contributes what requant
        rounded away: the quantized moment trajectory stays glued to
        the fp32 fused one (no drift), and so do the params."""
        params, grads = fo._gate_world(1)
        dense = fo.fused_sgd(0.1, 0.9, 1e-4, bucket_mb=0.05)
        quantized = fo.fused_sgd(0.1, 0.9, 1e-4, quant=quant,
                                 bucket_mb=0.05)
        p_a, s_a = fo._run_fused(dense, params, grads, 6)
        p_b, s_b = fo._run_fused(quantized, params, grads, 6)
        for m_fp32, plane in zip(s_a.m, s_b.m):
            m_q = ok.dequant_plane(plane, quant)
            assert float(jnp.max(jnp.abs(m_fp32 - m_q))) < 1e-3
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(p_a),
                                  jax.tree.leaves(p_b)))
        assert err < 1e-3


# -- (q, scale) leaves through checkpoint / reshard / migration -------------


def _simple_fused_state(n_devices=None, quant="int8", optimizer="adam",
                        seed=0):
    """A small TrainState on a fused tx; dp-sharded params when a
    device count is given, single-device otherwise."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(16, 128)).astype(np.float32)
    b = rng.normal(size=(128,)).astype(np.float32)
    if n_devices is not None:
        mesh = Mesh(np.array(jax.devices()[:n_devices]), ("dp",))
        params = {"w": jax.device_put(w, NamedSharding(mesh, P("dp"))),
                  "b": jax.device_put(b, NamedSharding(mesh, P()))}
    else:
        params = {"w": jnp.asarray(w), "b": jnp.asarray(b)}
    factory = fo.fused_adam if optimizer == "adam" else fo.fused_sgd
    tx = factory(1e-2, quant=quant, bucket_mb=0.05)
    return TrainState.create(apply_fn=None, params=params, tx=tx)


def _grads_like(params, seed=9):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(rng.normal(0, 0.02, size=p.shape)
                              .astype(np.float32)), params)


class TestQuantizedStateSerialization:
    def test_replicated_roundtrip_bitwise(self, tmp_path):
        state = _simple_fused_state()
        grads = _grads_like(state.params)
        for _ in range(2):
            state = state.apply_gradients(grads=grads)
        mgr = CheckpointManager(str(tmp_path / "c"), process_index=0)
        mgr.save(state, TrainStatus(epoch=0, step=2))
        restored, status = mgr.restore(_simple_fused_state(seed=5))
        assert status.step == 2
        assert_trees_bitwise(host_tree(state), host_tree(restored))
        # ... and the restored run CONTINUES bitwise (residuals intact)
        assert_trees_bitwise(
            host_tree(state.apply_gradients(grads=grads)),
            host_tree(restored.apply_gradients(grads=grads)))

    @pytest.mark.parametrize("tgt_n", [2, 8])
    def test_sharded_reshard_roundtrip_bitwise(self, tmp_path, tgt_n):
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-virtual-device test mesh")
        state = _simple_fused_state(n_devices=4)
        state = state.apply_gradients(grads=_grads_like(state.params))
        sc.save_sharded(str(tmp_path / "s"), state)
        fresh = _simple_fused_state(n_devices=tgt_n, seed=5)
        restored = sc.restore_sharded(str(tmp_path / "s"), fresh)
        assert_trees_bitwise(host_tree(state), host_tree(restored))

    def test_peer_restore_bitwise_and_byte_accounted(self, tmp_path):
        """A joiner assembling the fused state from a live donor gets
        the int8 planes bitwise — and pays quantized bytes on the wire
        (the donor advert quotes as-stored nbytes)."""
        import time

        from edl_tpu.collective import migration as mig
        from edl_tpu.coord.store import InMemStore

        if len(jax.devices()) < 4:
            pytest.skip("needs the 8-virtual-device test mesh")
        state = _simple_fused_state(n_devices=4)
        state = state.apply_gradients(grads=_grads_like(state.params))
        store = InMemStore()
        mgr = CheckpointManager(str(tmp_path / "c"), process_index=0,
                                sharded=True)
        svc = mig.MigrationService(store, "fjob", "pod0",
                                   addr="127.0.0.1")
        svc.attach(mgr)
        try:
            mgr.save(state, TrainStatus(epoch=0, step=1))
            deadline = time.monotonic() + 5.0
            while (not mig.live_donors(store, "fjob")
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            donors = mig.live_donors(store, "fjob")
            assert donors, "donor advert never appeared"
            state_nbytes = sum(x.nbytes
                               for x in jax.tree.leaves(host_tree(state)))
            assert donors[0]["nbytes"] == state_nbytes
            peer, _, stats = mig.restore_from_peers(
                store, "fjob", _simple_fused_state(n_devices=4, seed=5))
            assert_trees_bitwise(host_tree(state), host_tree(peer))
            assert stats["bytes_from_peers"] == state_nbytes
        finally:
            svc.shutdown(linger=False)

    def test_snapshot_nbytes_counts_as_stored(self):
        state = _simple_fused_state()
        snap = sc.snapshot_host_tree(state)
        expect = sum(x.nbytes for x in jax.tree.leaves(host_tree(state)))
        assert sc.snapshot_nbytes(snap) == expect
        # dict layout (sealed_snapshot's chunk map) counts the same
        assert sc.snapshot_nbytes(
            {"chunks": dict(snap["chunks"])}) == expect


# -- donation ----------------------------------------------------------------


def _tiny_loss(state, params, batch):
    return jnp.mean((batch["x"] @ params["w"]) ** 2), {}


class TestDonation:
    @pytest.mark.parametrize("mode", ["fp32", "int8"])
    def test_fused_step_donates_every_state_buffer(self, mode):
        quant = "off" if mode == "fp32" else mode
        params = {"w": jnp.ones((8, 128), jnp.float32)}
        batch = {"x": jnp.ones((4, 8), jnp.float32)}
        for tx in (fo.fused_sgd(0.1, 0.9, quant=quant, bucket_mb=0.05),
                   fo.fused_adam(1e-2, quant=quant, bucket_mb=0.05)):
            state = TrainState.create(apply_fn=None, params=params,
                                      tx=tx)
            cov = donation_coverage(make_train_step(_tiny_loss),
                                    state, batch)
            assert cov["full"], cov
            assert cov["aliased"] >= cov["state_leaves"]

    def test_donate_false_aliases_nothing(self):
        params = {"w": jnp.ones((8, 128), jnp.float32)}
        batch = {"x": jnp.ones((4, 8), jnp.float32)}
        state = TrainState.create(
            apply_fn=None, params=params,
            tx=fo.fused_sgd(0.1, 0.9, bucket_mb=0.05))
        cov = donation_coverage(
            make_train_step(_tiny_loss, donate=False), state, batch)
        assert cov["aliased"] == 0
        assert not cov["full"]


# -- remat knob --------------------------------------------------------------


class TestRematKnob:
    def test_choose_remat_by_footprint(self):
        from edl_tpu.models.transformer import (TransformerConfig,
                                                auto_remat, choose_remat)

        big = TransformerConfig(vocab_size=1000, d_model=1024,
                                n_heads=8, n_layers=24, d_ff=4096,
                                max_len=2048, dtype=jnp.float32)
        tiny = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                 n_layers=2, d_ff=64, max_len=64,
                                 dtype=jnp.float32)
        hbm = 16 * 2**30
        assert choose_remat(big, batch_size=64, hbm_bytes=hbm)
        assert not choose_remat(tiny, batch_size=4, hbm_bytes=hbm)
        assert auto_remat(big, 64, hbm_bytes=hbm).remat
        assert not auto_remat(tiny, 4, hbm_bytes=hbm).remat


# -- knobs -------------------------------------------------------------------


class TestKnobs:
    def test_make_fused_tx_modes(self):
        assert fo.make_fused_tx("sgdm", 0.1, "off") is None
        tx = fo.make_fused_tx("sgdm", 0.1, "fp32", momentum=0.8)
        assert isinstance(tx, fo.FusedOptimizer)
        assert tx.quant == "off" and tx.momentum == 0.8
        tx = fo.make_fused_tx("adam", 0.1, "int8")
        assert tx.optimizer == "adam" and tx.quant == "int8"
        with pytest.raises(ValueError, match="EDL_TPU_FUSED_OPT"):
            fo.make_fused_tx("sgdm", 0.1, "int4")

    def test_validation(self):
        with pytest.raises(ValueError, match="optimizer"):
            fo.FusedOptimizer("rmsprop", 0.1)
        with pytest.raises(ValueError, match="quant"):
            fo.FusedOptimizer("sgdm", 0.1, quant="int4")
        with pytest.raises(ValueError, match="bucket_mb"):
            fo.FusedOptimizer("sgdm", 0.1, bucket_mb=0)
        with pytest.raises(NotImplementedError, match="fused_apply"):
            fo.fused_sgd(0.1).update({}, None)
        with pytest.raises(ValueError, match="float params only"):
            fo.fused_sgd(0.1).init({"ids": jnp.zeros((8,), jnp.int32)})

    def test_fused_mode_env_knob(self, monkeypatch):
        monkeypatch.delenv("EDL_TPU_FUSED_OPT", raising=False)
        assert fo.fused_mode() == "off"
        assert fo.make_fused_tx("adam", 0.1) is None
        monkeypatch.setenv("EDL_TPU_FUSED_OPT", "int8")
        assert fo.fused_mode() == "int8"
        assert fo.make_fused_tx("adam", 0.1).quant == "int8"
        assert fo.fused_mode("fp32") == "fp32"   # a flag given wins
        assert fo.make_fused_tx("adam", 0.1, "off") is None
        monkeypatch.setenv("EDL_TPU_FUSED_OPT", "int4")
        with pytest.raises(ValueError, match="EDL_TPU_FUSED_OPT"):
            fo.fused_mode()

    def test_opt_state_bytes_cut(self):
        params, _ = fo._gate_world(0)
        dense = fo.fused_sgd(0.1, bucket_mb=0.05).init(params)
        quant = fo.fused_sgd(0.1, quant="int8",
                             bucket_mb=0.05).init(params)
        assert (fo.opt_state_bytes(dense)
                >= 1.8 * fo.opt_state_bytes(quant))
