"""Gradient-path equivalence contract of the DCN-aware comm plane.

The load-bearing claims of edl_tpu/train/comm.py, each pinned:

- bucketing is numerics-free: bucketed-DENSE on the flat world is
  BITWISE identical to the plain jit step (reduction is elementwise;
  the 1/W scaling is exact on power-of-two worlds);
- the hierarchical decomposition is a re-associated sum: the 2-slice
  hybrid dryrun holds loss parity at float tolerance;
- compression never loses gradient mass: the error-feedback residual
  carries exactly what the top-k wire dropped, and re-contributes it;
- bucket-plan edges: 0-d leaves, ragged tails, dtype grouping,
  oversized leaves;
- the int8 wire (ops/pack.py): XLA fallback == Pallas interpret
  kernel, bounded quantization error, exact zero round-trip.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.models.mlp import MLP
from edl_tpu.parallel import mesh as mesh_lib
from edl_tpu.parallel.compat import shard_map
from edl_tpu.train import comm
from edl_tpu.train.state import TrainState
from edl_tpu.train.step import make_train_step

WORLD = 8


def _mlp_problem(seed: int = 0, hidden=(32, 16), classes: int = 4,
                 rows: int = 16):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, 8)).astype(np.float32)
    y = rng.integers(0, classes, size=rows).astype(np.int32)
    model = MLP(num_classes=classes, hidden=hidden)
    params = model.init(jax.random.PRNGKey(seed), jnp.asarray(x))["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.sgd(0.1, momentum=0.9))

    def loss_fn(state, params, batch):
        logits = state.apply_fn({"params": params}, batch["x"])
        onehot = jax.nn.one_hot(batch["y"], classes)
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot,
                                 axis=-1))
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"])
                       .astype(jnp.float32))
        return loss, {"acc": acc}

    return loss_fn, state, {"x": x, "y": y}


def _replicate(mesh, tree):
    return jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P())), tree)


# -- bucket planning --------------------------------------------------------


def test_plan_buckets_greedy_fill_and_padding():
    params = {"a": jnp.zeros((100,)), "b": jnp.zeros((100,)),
              "c": jnp.zeros((1000,))}
    # 150 floats = 600B budget: a+b exceed it -> a alone, then b, then
    # the oversized c gets its own bucket
    plan = comm.plan_buckets(params, bucket_mb=600 / (1 << 20), align=8)
    sizes = [b.size for b in plan.buckets]
    assert sizes == [100, 100, 1000]
    for b in plan.buckets:
        assert b.padded % 8 == 0
        assert b.padded >= b.size
    assert plan.buckets[0].padded == 104  # ragged tail padded up


def test_plan_buckets_groups_by_dtype_and_keeps_scalars():
    params = {"w": jnp.zeros((64,), jnp.float32),
              "n": jnp.zeros((), jnp.int32),       # 0-d leaf
              "v": jnp.zeros((8,), jnp.float32)}
    plan = comm.plan_buckets(params, bucket_mb=4.0, align=8)
    dtypes = sorted(str(b.dtype) for b in plan.buckets)
    assert dtypes == ["float32", "int32"]
    assert plan.n_leaves == 3
    int_bucket = next(b for b in plan.buckets
                      if b.dtype == jnp.int32)
    assert int_bucket.size == 1 and int_bucket.padded == 8


def test_plan_buckets_deterministic():
    params = {"a": jnp.zeros((37,)), "b": jnp.zeros((113,))}
    p1 = comm.plan_buckets(params, 0.001, align=8)
    p2 = comm.plan_buckets(params, 0.001, align=8)
    assert p1.buckets == p2.buckets


def test_pack_unpack_roundtrip_bitwise():
    rng = np.random.default_rng(3)
    tree = {"a": jnp.asarray(rng.normal(size=(7, 3)).astype(np.float32)),
            "s": jnp.asarray(np.float32(rng.normal())),  # 0-d
            "b": jnp.asarray(rng.normal(size=(33,)).astype(np.float32))}
    plan = comm.plan_buckets(tree, bucket_mb=0.0001, align=8)
    bufs = comm.pack_buckets(tree, plan)
    for buf, b in zip(bufs, plan.buckets):
        assert buf.shape == (b.padded,)
    out = comm.unpack_buckets(bufs, plan)
    assert comm.tree_bitwise_equal(tree, out)


# -- the equivalence contract ----------------------------------------------


def test_bucketed_dense_bitwise_with_jit():
    """The tentpole gate: flat bucketed-dense == plain jit, bitwise,
    over multiple steps (params AND loss)."""
    loss_fn, state, batch = _mlp_problem()
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    placed = mesh_lib.shard_batch(mesh, batch)
    jit_step = make_train_step(loss_fn, donate=False)
    comm_step = comm.make_comm_train_step(
        loss_fn, mesh=mesh, donate=False,
        config=comm.CommConfig(bucket_mb=0.001))
    s1, s2 = _replicate(mesh, state), _replicate(mesh, state)
    for _ in range(3):
        s1, m1 = jit_step(s1, placed)
        s2, m2 = comm_step(s2, placed)
        assert float(m1["loss"]) == float(m2["loss"])
        assert comm.tree_bitwise_equal(s1.params, s2.params)
    assert comm_step.plan.n_buckets > 1  # multiple buckets exercised


def test_hybrid_two_slice_dryrun_loss_parity():
    """The 2-slice dryrun term: hierarchical dense (reduce-scatter ->
    cross-slice psum -> all-gather) against the flat jit trajectory —
    a re-associated sum, loss parity at float tolerance."""
    loss_fn, state, batch = _mlp_problem(seed=1)
    topo = mesh_lib.SliceTopology(2, WORLD // 2)
    hybrid = mesh_lib.make_hybrid_mesh(mesh_lib.MeshSpec({"dp": -1}),
                                       topo)
    flat = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    jit_step = make_train_step(loss_fn, donate=False)
    comm_step = comm.make_comm_train_step(
        loss_fn, mesh=hybrid, topology=topo, donate=False,
        config=comm.CommConfig(bucket_mb=0.001))
    s1 = _replicate(flat, state)
    s2 = _replicate(hybrid, state)
    fb = mesh_lib.shard_batch(flat, batch)
    hb = mesh_lib.shard_batch(hybrid, batch)
    for _ in range(3):
        s1, m1 = jit_step(s1, fb)
        s2, m2 = comm_step(s2, hb)
        assert float(m2["loss"]) == pytest.approx(float(m1["loss"]),
                                                  abs=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        s1.params, s2.params)
    assert comm_step.dcn_bytes_per_step() > 0


def test_parity_gate_reports_ok():
    loss_fn, state, batch = _mlp_problem(seed=2)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    gate = comm.loss_parity_gate(
        loss_fn, state, batch, mesh=mesh,
        config=comm.CommConfig(bucket_mb=0.001, compress="topk",
                               topk_frac=0.25, min_compress_elems=16),
        steps=2, envelope=0.2)
    assert gate["bitwise_dense"] is True
    assert gate["dense_loss_delta"] == 0.0
    assert "max_loss_delta" in gate and gate["loss_envelope_ok"]
    assert gate["ok"]


# -- sparse cross-slice leg -------------------------------------------------


def _run_cross_topk(values: np.ndarray, resid: np.ndarray, k: int):
    """Drive _cross_topk under shard_map on the flat dp axis (every
    chip its own slice): values/resid are (W, m) per-device rows."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    groups = [list(range(WORLD))]

    def fn(v, e):
        out, e2 = comm._cross_topk(v.reshape(-1), e.reshape(-1), "dp",
                                   groups, k)
        return out.reshape(1, -1), e2.reshape(1, -1)

    f = shard_map(fn, mesh=mesh, in_specs=(P("dp"), P("dp")),
                  out_specs=(P(), P("dp")))
    return f(jnp.asarray(values), jnp.asarray(resid))


def test_sparse_topk_full_k_matches_dense_psum():
    rng = np.random.default_rng(7)
    v = rng.normal(size=(WORLD, 24)).astype(np.float32)
    out, resid = _run_cross_topk(v, np.zeros_like(v), k=24)
    np.testing.assert_allclose(np.asarray(out)[0], v.sum(0), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(resid), 0.0, atol=1e-7)


def test_sparse_topk_conserves_gradient_mass():
    """sent + residual == contribution, per chip — nothing is lost,
    only deferred (the error-feedback invariant)."""
    rng = np.random.default_rng(8)
    v = rng.normal(size=(WORLD, 32)).astype(np.float32)
    out, resid = _run_cross_topk(v, np.zeros_like(v), k=4)
    # the reduced result plus every chip's residual re-adds to the
    # dense sum
    np.testing.assert_allclose(
        np.asarray(out)[0] + np.asarray(resid).sum(0), v.sum(0),
        rtol=1e-5, atol=1e-6)
    # each chip kept exactly k entries; the rest sit in its residual
    assert ((np.asarray(resid) != 0).sum(axis=1) == 32 - 4).all()


def test_residual_carryover_across_steps():
    """A value too small to make step 1's top-k accumulates in the
    residual and ships once it dominates — DGC's deferred send."""
    v = np.zeros((WORLD, 16), np.float32)
    v[0, :4] = [10.0, 9.0, 8.0, 7.0]  # chip 0's big entries
    v[0, 5] = 0.6                      # small: dropped at k=4
    out1, resid1 = _run_cross_topk(v, np.zeros_like(v), k=4)
    assert float(np.asarray(out1)[0, 5]) == 0.0
    assert float(np.asarray(resid1)[0, 5]) == pytest.approx(0.6)
    # step 2: same small value again; 0.6 + 0.6 rides the residual.
    # big entries zero this step, so the deferred mass dominates.
    v2 = np.zeros_like(v)
    v2[0, 5] = 0.6
    out2, resid2 = _run_cross_topk(v2, np.asarray(resid1), k=4)
    assert float(np.asarray(out2)[0, 5]) == pytest.approx(1.2)
    assert float(np.asarray(resid2)[0, 5]) == 0.0


def test_compressed_step_threads_residual_state():
    """Integration: the CommTrainStep's residual cell is live — after a
    topk step the stored comm state is nonzero and has the (W, m)
    dp-sharded layout."""
    loss_fn, state, batch = _mlp_problem(seed=3)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    step = comm.make_comm_train_step(
        loss_fn, mesh=mesh, donate=False,
        config=comm.CommConfig(bucket_mb=0.001, compress="topk",
                               topk_frac=0.125, min_compress_elems=16))
    placed = mesh_lib.shard_batch(mesh, batch)
    s = _replicate(mesh, state)
    s, _ = step(s, placed)
    assert step._comm, "no residual state threaded"
    total = 0.0
    for r, b in zip(step._comm, step.plan.buckets):
        if r.shape[1]:
            assert r.shape == (WORLD, b.padded)  # chips=1: full bucket
            total += float(jnp.sum(jnp.abs(r)))
    assert total > 0.0


# -- int8 wire --------------------------------------------------------------


def test_int8_pack_roundtrip_error_bounded():
    from edl_tpu.ops.pack import pack_int8, unpack_int8
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(300,)).astype(np.float32))
    q, scale = pack_int8(x)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    err = np.abs(np.asarray(unpack_int8(q, scale)) - np.asarray(x))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_int8_pack_zero_vector_exact():
    from edl_tpu.ops.pack import pack_int8, unpack_int8
    q, scale = pack_int8(jnp.zeros((64,)))
    assert float(scale) == 1.0
    assert not np.asarray(q).any()
    assert not np.asarray(unpack_int8(q, scale)).any()


def test_int8_pallas_kernel_matches_xla(monkeypatch):
    from edl_tpu.ops import pack as pack_mod
    rng = np.random.default_rng(10)
    # ragged length: exercises the lane-padding path in the kernel
    x = jnp.asarray(rng.normal(size=(200,)).astype(np.float32))
    q_xla, s_xla = pack_mod._pack_xla(x)
    monkeypatch.setattr(pack_mod, "_FORCE_INTERPRET", True)
    q_k, s_k = pack_mod.pack_int8(x)
    assert float(s_xla) == pytest.approx(float(s_k), rel=1e-6)
    np.testing.assert_array_equal(np.asarray(q_xla), np.asarray(q_k))


def test_int8_step_tracks_dense_within_envelope():
    loss_fn, state, batch = _mlp_problem(seed=4)
    topo = mesh_lib.SliceTopology(2, WORLD // 2)
    mesh = mesh_lib.make_hybrid_mesh(mesh_lib.MeshSpec({"dp": -1}), topo)
    gate = comm.loss_parity_gate(
        loss_fn, state, batch, mesh=mesh, topology=topo,
        config=comm.CommConfig(bucket_mb=0.001, compress="int8",
                               min_compress_elems=16),
        steps=3, envelope=5e-3)
    assert gate["loss_envelope_ok"], gate


# -- knobs / validation / wiring -------------------------------------------


def test_comm_config_env_knobs(monkeypatch):
    from edl_tpu.utils.config import from_env, given
    for name in ("EDL_TPU_DCN_COMPRESS", "EDL_TPU_COMM_BUCKET_MB",
                 "EDL_TPU_MOE_DISPATCH", "EDL_TPU_MOE_COMPRESS"):
        monkeypatch.delenv(name, raising=False)
    # neither a flag nor the environment asks: no manual step
    assert comm.CommConfig.from_flags(bucket_mb=None, compress=None) is None
    assert comm.CommConfig().target_mb == comm.DEFAULT_BUCKET_MB
    # a compressed wire alone buckets at the default target
    cfg = comm.CommConfig.from_flags(compress="int8")
    assert cfg.asked and cfg.target_mb == comm.DEFAULT_BUCKET_MB
    monkeypatch.setenv("EDL_TPU_DCN_COMPRESS", "topk")
    monkeypatch.setenv("EDL_TPU_COMM_BUCKET_MB", "2.5")
    cfg = from_env(comm.CommConfig)
    assert cfg.asked and cfg.compress == "topk" and cfg.target_mb == 2.5
    assert comm.CommConfig.from_flags() == cfg
    # a flag given wins over the environment, 0 MiB and 'off' included
    assert comm.CommConfig.from_flags(bucket_mb=0.0, compress="off") is None
    monkeypatch.setenv("EDL_TPU_MOE_DISPATCH", "flat")
    assert from_env(comm.MoEDispatchConfig).mode == "flat"
    moe = from_env(comm.MoEDispatchConfig,
                   **given(mode="hier", compress="int8"))
    assert (moe.mode, moe.compress) == ("hier", "int8")


def test_comm_config_validation():
    with pytest.raises(ValueError):
        comm.CommConfig(compress="gzip")
    with pytest.raises(ValueError):
        comm.CommConfig(bucket_mb=-1)
    with pytest.raises(ValueError):
        comm.CommConfig(topk_frac=0.0)


def test_comm_step_builder_needs_a_dp_mesh():
    loss_fn, state, batch = _mlp_problem()
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    cfg = comm.CommConfig(bucket_mb=1.0)
    step = comm.make_comm_train_step(loss_fn, mesh=mesh, config=cfg)
    assert isinstance(step, comm.CommTrainStep)
    with pytest.raises(TypeError):
        comm.make_comm_train_step(loss_fn, config=cfg)  # no mesh
    with pytest.raises(ValueError, match="dp axis"):
        comm.make_comm_train_step(
            loss_fn, config=cfg,
            mesh=mesh_lib.make_mesh(mesh_lib.MeshSpec({"fsdp": -1})))


def test_non_dp_mesh_rejected():
    loss_fn, *_ = _mlp_problem()
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1, "tp": 2}))
    with pytest.raises(ValueError, match="dp-only"):
        comm.make_comm_train_step(loss_fn, mesh=mesh,
                                  config=comm.CommConfig())
    with pytest.raises(ValueError, match="n_slices"):
        comm.make_comm_train_step(
            loss_fn, mesh=mesh_lib.make_mesh(mesh_lib.MeshSpec(
                {"dp": -1})),
            topology=mesh_lib.SliceTopology(3, 2),
            config=comm.CommConfig())


def test_stats_and_obs_counter():
    from edl_tpu.obs import metrics as obs_metrics
    loss_fn, state, batch = _mlp_problem(seed=5)
    topo = mesh_lib.SliceTopology(2, WORLD // 2)
    mesh = mesh_lib.make_hybrid_mesh(mesh_lib.MeshSpec({"dp": -1}), topo)
    step = comm.make_comm_train_step(
        loss_fn, mesh=mesh, topology=topo, donate=False,
        config=comm.CommConfig(bucket_mb=0.001))
    counter = obs_metrics.registry().counter("step_dcn_bytes")
    before = counter.value
    placed = mesh_lib.shard_batch(mesh, batch)
    s = _replicate(mesh, state)
    s, _ = step(s, placed)
    s, _ = step(s, placed)
    stats = step.stats()
    assert stats["comm_steps"] == 2
    assert stats["dcn_bytes_per_step"] > 0
    assert stats["dcn_overlap_pct"] > 0  # multi-bucket plan
    assert counter.value - before == 2 * stats["dcn_bytes_per_step"]


def test_batch_stats_model_trains_under_comm_path():
    """BN models ride the comm path: batch_stats fold in (pmean across
    shards — the documented delta vs global-batch stats) and training
    matches jit within tolerance."""
    import flax.linen as nn
    from edl_tpu.train import classification as cls

    class TinyBN(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = nn.Conv(8, (3, 3))(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            x = nn.relu(x).reshape((x.shape[0], -1))
            return nn.Dense(4)(x)

    rng = np.random.default_rng(6)
    x = rng.normal(size=(16, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 4, size=16).astype(np.int32)
    model = TinyBN()
    state = cls.create_state(model, jax.random.PRNGKey(0), (1, 8, 8, 3),
                             optax.sgd(0.05))
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))

    def loss_fn(state, params, batch):
        variables = {"params": params, "batch_stats": state.batch_stats}
        logits, mutated = state.apply_fn(variables, batch["image"],
                                         train=True,
                                         mutable=["batch_stats"])
        onehot = jax.nn.one_hot(batch["label"], 4)
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot,
                                 axis=-1))
        return loss, {"batch_stats": mutated["batch_stats"]}

    placed = mesh_lib.shard_batch(mesh, {"image": x, "label": y})
    jit_step = make_train_step(loss_fn, donate=False)
    comm_step = comm.make_comm_train_step(
        loss_fn, mesh=mesh, donate=False,
        config=comm.CommConfig(bucket_mb=0.001))
    s1, s2 = _replicate(mesh, state), _replicate(mesh, state)
    for _ in range(2):
        s1, m1 = jit_step(s1, placed)
        s2, m2 = comm_step(s2, placed)
    # BN under the manual path normalizes PER SHARD (the reference's
    # per-GPU convention); the jit path normalizes over the global
    # batch — a documented semantic delta, bounded by the envelope
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]),
                                              abs=0.05)
    # shard-mean of means == global mean; variances differ by the
    # between-shard variance term — loose tolerance on the stats tree
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=0.15),
        s1.batch_stats, s2.batch_stats)


def test_dense_path_residual_state_is_empty_width():
    loss_fn, state, batch = _mlp_problem(seed=7)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    step = comm.make_comm_train_step(
        loss_fn, mesh=mesh, donate=False,
        config=comm.CommConfig(bucket_mb=0.001))
    placed = mesh_lib.shard_batch(mesh, batch)
    s = _replicate(mesh, state)
    step(s, placed)
    assert all(r.shape[1] == 0 for r in step._comm)
    assert step.dcn_bytes_per_step() == 0  # flat world, dense: no DCN


def test_dcn_reduce_span_emitted_when_tracing(monkeypatch):
    """The obs satellite: with tracing on, every comm-step dispatch
    rides a `step.dcn_reduce` span carrying the wire accounting."""
    import contextlib

    from edl_tpu.obs import trace

    calls = []

    @contextlib.contextmanager
    def fake_span(name, parent=None, attrs=None):
        calls.append((name, attrs))
        yield None

    monkeypatch.setattr(trace, "enabled", lambda: True)
    monkeypatch.setattr(trace, "span", fake_span)
    loss_fn, state, batch = _mlp_problem(seed=8)
    topo = mesh_lib.SliceTopology(2, WORLD // 2)
    mesh = mesh_lib.make_hybrid_mesh(mesh_lib.MeshSpec({"dp": -1}), topo)
    step = comm.make_comm_train_step(
        loss_fn, mesh=mesh, topology=topo, donate=False,
        config=comm.CommConfig(bucket_mb=0.001))
    s = _replicate(mesh, state)
    step(s, mesh_lib.shard_batch(mesh, batch))
    assert calls and calls[0][0] == "step.dcn_reduce"
    assert calls[0][1]["dcn_bytes"] == step.dcn_bytes_per_step()
    assert calls[0][1]["buckets"] == step.plan.n_buckets


def test_sparse_psum_axis_index_groups_scope_reduction():
    """dgc.sparse_psum grown group scoping: with axis_index_groups the
    top-k exchange stays INSIDE each group (the hierarchical DCN-leg
    contract — mesh.dp_comm_groups feeds exactly these lists)."""
    from edl_tpu.train import dgc

    intra, _ = mesh_lib.dp_comm_groups(2, WORLD // 2)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    rng = np.random.default_rng(11)
    v = rng.normal(size=(WORLD, 64)).astype(np.float32)

    def fn(x):
        out = dgc.sparse_psum({"g": x.reshape(-1)}, "dp", keep_frac=1.0,
                              axis_index_groups=intra)
        return out["g"].reshape(1, -1)

    out = shard_map(fn, mesh=mesh, in_specs=(P("dp"),),
                    out_specs=P("dp"))(jnp.asarray(v))
    out = np.asarray(out)
    # every device holds ITS group's sum, not the global sum
    np.testing.assert_allclose(out[0], v[:4].sum(0), rtol=1e-6)
    np.testing.assert_allclose(out[7], v[4:].sum(0), rtol=1e-6)
    assert not np.allclose(out[0], v.sum(0))

    # sparse path (k=1 per worker): contributions stay group-local
    one = np.zeros((WORLD, 64), np.float32)
    one[0, 3] = 5.0   # group 0's only mass
    one[4, 9] = -7.0  # group 1's only mass

    def fn2(x):
        out = dgc.sparse_psum({"g": x.reshape(-1)}, "dp",
                              keep_frac=1 / 64,
                              axis_index_groups=intra)
        return out["g"].reshape(1, -1)

    out2 = np.asarray(shard_map(fn2, mesh=mesh, in_specs=(P("dp"),),
                                out_specs=P("dp"))(jnp.asarray(one)))
    assert out2[0, 3] == 5.0 and out2[0, 9] == 0.0
    assert out2[7, 9] == -7.0 and out2[7, 3] == 0.0
