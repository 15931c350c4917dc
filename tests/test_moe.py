"""Elastic MoE: router semantics, dispatch-wire parity, ep plumbing.

The load-bearing claims of the expert-parallel plane, each pinned:

- the top-k capacity-factor router (models/transformer.router_topk)
  grants slots choice-major, drops past capacity, and accounts every
  drop (combine/dispatch zero out together; dropped_frac is exact);
- the hierarchical all-to-all (train/comm.moe_all_to_all) is a pure
  permutation: BITWISE identical to the flat single collective when
  uncompressed, on the emulated 2x4 world and through a real training
  step (moe_parity_gate);
- the int8 DCN leg rides the SHARED quantizer (ops/pack.py) — the
  wire decomposes into per-destination pack_int8 exactly, so the
  interpret-mode kernel pin on pack_int8 covers it;
- ep mesh plumbing: MeshSpec.resolve_hybrid lets `ep` carry the DCN
  dimension, ep_comm_groups mirrors dp_comm_groups, and the MoE step
  rejects meshes it does not own;
- the obs surface: `step.moe_dispatch` span + `step_moe_dcn_bytes`
  counter carry the wire accounting.

ep-resize bitwise restore (expert tables through the checkpoint /
migration planner) lives in tests/test_state_migration.py.
"""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.models import transformer as tfm
from edl_tpu.parallel import mesh as mesh_lib
from edl_tpu.parallel.compat import shard_map
from edl_tpu.train import comm

WORLD = 8


# -- router ------------------------------------------------------------------


def test_moe_capacity_arithmetic():
    # ceil(1.25 * 64 * 2 / 8) = 20
    assert tfm.moe_capacity(64, 8, 2, 1.25) == 20
    assert tfm.moe_capacity(1, 64, 1, 0.1) == 1  # floor at 1
    assert tfm.moe_capacity(16, 4, 1, 1.0) == 4


def test_router_topk_shapes_and_renormalized_gates():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(12, 4)).astype(np.float32))
    # capacity == T: no expert can overflow, whatever the routing
    combine, dispatch, aux = tfm.router_topk(logits, top_k=2,
                                             capacity=12)
    assert combine.shape == (12, 4, 12) and dispatch.shape == (12, 4, 12)
    assert dispatch.dtype == jnp.bool_
    # nothing dropped at this capacity -> each token's kept gates sum to 1
    assert float(aux["dropped_frac"]) == 0.0
    np.testing.assert_allclose(np.asarray(combine).sum(axis=(1, 2)),
                               1.0, rtol=1e-5)


def test_router_capacity_drop_is_exact_and_choice_major():
    """All 8 tokens pick experts (0, 1); capacity 3 keeps the FIRST
    three first-choice assignments per expert and drops the rest —
    10 of 16 assignments, and every dropped assignment vanishes from
    dispatch AND combine."""
    t, e, cap = 8, 4, 3
    logits = np.full((t, e), -10.0, np.float32)
    logits[:, 0] = 2.0   # every token's first choice
    logits[:, 1] = 1.0   # every token's second choice
    combine, dispatch, aux = tfm.router_topk(jnp.asarray(logits),
                                             top_k=2, capacity=cap)
    d = np.asarray(dispatch)
    assert d[:, 0].sum() == cap and d[:, 1].sum() == cap
    assert d[:, 2:].sum() == 0                      # untouched experts
    # choice-major: expert 0's slots go to tokens 0..2, THEN expert 1's
    # to tokens 0..2 (second choices of the earliest tokens)
    assert d[:3, 0].sum() == cap and d[3:, 0].sum() == 0
    assert float(aux["dropped_frac"]) == pytest.approx(10 / 16)
    c = np.asarray(combine)
    assert (c[d] > 0).all() and (c[~d] == 0).all()


def test_router_perfect_balance_scores_one():
    """One token per expert, uniform probs -> Shazeer load_balance == 1
    (its minimum under a fixed top_k) up to softmax float noise."""
    e = 4
    logits = jnp.asarray(np.zeros((8, e), np.float32))
    _, _, aux = tfm.router_topk(logits, top_k=1, capacity=8)
    assert float(aux["load_balance"]) == pytest.approx(1.0, rel=1e-5)


def test_transformer_config_moe_validation():
    common = dict(vocab_size=8, d_model=8, n_heads=1, n_layers=1,
                  d_ff=8, max_len=4)
    with pytest.raises(ValueError, match="n_experts"):
        tfm.TransformerConfig(**common, moe=True, n_experts=1)
    with pytest.raises(ValueError, match="moe_top_k"):
        tfm.TransformerConfig(**common, moe=True, n_experts=4,
                              moe_top_k=5)
    with pytest.raises(ValueError, match="moe_capacity_factor"):
        tfm.TransformerConfig(**common, moe=True, n_experts=4,
                              moe_capacity_factor=0.0)
    # moe=False skips the expert checks entirely
    tfm.TransformerConfig(**common, n_experts=1)


def test_lm_loss_fn_collects_router_aux():
    cfg = tfm.TransformerConfig(vocab_size=16, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_len=8,
                                dtype=jnp.float32, moe=True, n_experts=4)
    model = tfm.Transformer(cfg)
    toks = jnp.asarray(np.arange(32, dtype=np.int32).reshape(4, 8) % 16)
    from flax.core import meta
    variables = meta.unbox(model.init(jax.random.PRNGKey(0), toks,
                                      train=False))
    from edl_tpu.train.state import TrainState
    import optax
    state = TrainState.create(apply_fn=model.apply,
                              params=variables["params"],
                              tx=optax.sgd(0.1))
    loss, metrics = tfm.lm_loss_fn(state, state.params,
                                    {"tokens": toks})
    assert float(loss) > 0
    assert {"ppl", "moe_balance", "moe_dropped"} <= set(metrics)
    assert float(metrics["moe_balance"]) > 0  # n_layers=2 MoE blocks sown
    assert 0.0 <= float(metrics["moe_dropped"]) <= 1.0


# -- ep mesh plumbing --------------------------------------------------------


def test_dcn_axis_of_prefers_ep():
    assert mesh_lib.dcn_axis_of({"dp": 4}) == "dp"
    assert mesh_lib.dcn_axis_of({"ep": 4}) == "ep"
    assert mesh_lib.dcn_axis_of({"dp": 2, "ep": 4}) == "ep"


def test_resolve_hybrid_ep_carries_dcn():
    spec = mesh_lib.MeshSpec({"ep": -1})
    topo = mesh_lib.SliceTopology(2, 4)
    dcn, ici = spec.resolve_hybrid(topo)
    assert dcn == {"ep": 2} and ici == {"ep": 4}
    with pytest.raises(ValueError, match="not divisible by n_slices"):
        mesh_lib.MeshSpec({"ep": 3}).resolve_hybrid(topo)
    with pytest.raises(ValueError, match="carry the DCN"):
        mesh_lib.MeshSpec({"tp": 8}).resolve_hybrid(topo)


def test_ep_comm_groups_mirror_dp():
    assert mesh_lib.ep_comm_groups(2, 4) == mesh_lib.dp_comm_groups(2, 4)
    intra, cross = mesh_lib.ep_comm_groups(2, 4)
    assert intra == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert cross == [[0, 4], [1, 5], [2, 6], [3, 7]]
    with pytest.raises(ValueError, match="positive factors"):
        mesh_lib.ep_comm_groups(0, 4)


def test_expert_tables_shard_over_ep():
    from edl_tpu.parallel.sharding import logical_to_spec
    mesh = _ep_mesh()
    # ep-only mesh: expert dim shards, embed/mlp (fsdp/tp) drop out
    assert logical_to_spec(("expert", "embed", "mlp"),
                           mesh=mesh) == P("ep")
    # the router stays replicated — every chip routes against all experts
    assert logical_to_spec(("embed", "expert_router"), mesh=mesh) == P()


def test_moe_dispatch_config_validation():
    with pytest.raises(ValueError, match="mode"):
        comm.MoEDispatchConfig(mode="ring")
    with pytest.raises(ValueError, match="compress"):
        comm.MoEDispatchConfig(compress="topk")
    with pytest.raises(ValueError, match="hier"):
        comm.MoEDispatchConfig(mode="flat", compress="int8")


def test_moe_step_rejects_foreign_meshes():
    lf = lambda wire: None  # noqa: E731 — never reached
    dp = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    with pytest.raises(ValueError, match="needs an ep axis"):
        comm.make_moe_comm_step(lf, mesh=dp)
    mixed = mesh_lib.make_mesh(mesh_lib.MeshSpec({"ep": -1, "tp": 2}))
    with pytest.raises(ValueError, match="ep-only"):
        comm.make_moe_comm_step(lf, mesh=mixed)


# -- the dispatch wire -------------------------------------------------------


def _ep_mesh(topo=None):
    if topo is not None:
        return mesh_lib.make_hybrid_mesh(mesh_lib.MeshSpec({"ep": -1}),
                                         topo)
    return mesh_lib.make_mesh(mesh_lib.MeshSpec({"ep": -1}))


def _run_a2a(x, **kw):
    """Drive moe_all_to_all under shard_map over the full ep axis."""
    mesh = _ep_mesh(kw.pop("topo", None))
    fn = functools.partial(comm.moe_all_to_all, axis="ep", **kw)
    return np.asarray(shard_map(fn, mesh=mesh, in_specs=(P("ep"),),
                                out_specs=P("ep"))(jnp.asarray(x)))


def test_hier_all_to_all_bitwise_with_flat():
    """The tentpole permutation claim, on the emulated 2x4 world: ICI
    leg + DCN leg == one flat collective, bitwise."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(WORLD * WORLD, 3, 5)).astype(np.float32)
    topo = mesh_lib.SliceTopology(2, 4)
    flat = _run_a2a(x, n_slices=2, chips=4, mode="flat", topo=topo)
    hier = _run_a2a(x, n_slices=2, chips=4, mode="hier", topo=topo)
    np.testing.assert_array_equal(flat, hier)
    # degenerate S=W decomposition (the flat-world compress path) is
    # the same permutation too
    hier_w = _run_a2a(x, n_slices=WORLD, chips=1, mode="hier")
    np.testing.assert_array_equal(flat, hier_w)


def test_hier_all_to_all_int8_bounded_and_per_dest_scaled():
    """int8 only touches the DCN leg, with one scale per (sender,
    destination-slice) chunk: payloads bound for different slices keep
    INDEPENDENT scales, so a slice receiving only small tokens gets a
    small-scale error bound — one global scale would crush it."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(WORLD * WORLD, 4, 4)).astype(np.float32)
    # destination-major rows w*W + d: everything bound for slice 0
    # (d < 4) is 100x the slice-1 payloads
    dest = np.arange(WORLD * WORLD) % WORLD
    x[dest < 4] *= 100.0
    topo = mesh_lib.SliceTopology(2, 4)
    dense = _run_a2a(x, n_slices=2, chips=4, mode="hier", topo=topo)
    q = _run_a2a(x, n_slices=2, chips=4, mode="hier", compress="int8",
                 topo=topo)
    assert q.dtype == np.float32
    err = np.abs(q - dense)
    # received rows w*W + s at chips of slice 1 carry only small
    # payloads: their bound follows the SMALL chunks' amax
    recv_chip = np.arange(WORLD * WORLD) // WORLD
    small = recv_chip >= 4
    small_amax = np.abs(dense[small]).max()
    assert err[small].max() <= small_amax / 254 * 1.05 + 1e-6
    # ...which is far tighter than a global-scale bound would allow
    assert np.abs(dense[~small]).max() / 254 > 10 * err[small].max()


def test_a2a_int8_wire_is_the_shared_quantizer():
    """ops/pack.all_to_all_int8 == per-destination pack_int8 +
    the same permutation, bitwise — so the interpret-mode kernel pin
    on pack_int8 (test_comm_overlap) covers this wire too."""
    from edl_tpu.ops.pack import all_to_all_int8, pack_int8, \
        unpack_int8
    rng = np.random.default_rng(3)
    g = WORLD
    x = rng.normal(size=(g * g, 6)).astype(np.float32)
    mesh = _ep_mesh()

    def wire(v):
        return all_to_all_int8(v, "ep")

    got = np.asarray(shard_map(wire, mesh=mesh, in_specs=(P("ep"),),
                               out_specs=P("ep"))(jnp.asarray(x)))
    # reference: quantize every destination block locally, permute
    # blocks exactly as the flat tiled all_to_all does
    per_chip = x.reshape(g, g, 6)
    rq = np.empty_like(per_chip)
    for s in range(g):
        for d_ in range(g):
            q, sc = pack_int8(jnp.asarray(per_chip[s, d_]))
            rq[s, d_] = np.asarray(unpack_int8(q, sc))
    want = rq.transpose(1, 0, 2).reshape(g * g, 6)
    np.testing.assert_array_equal(got, want)


def test_moe_wire_combine_inverts_dispatch():
    """combine(dispatch(buf)) == buf bitwise: the two transports are
    inverse permutations (all_to_all is an involution on the block
    grid), so a no-op expert returns every token slot untouched."""
    e, cap, d = WORLD * 2, 3, 4
    rng = np.random.default_rng(4)
    x = rng.normal(size=(WORLD * e, cap, d)).astype(np.float32)
    mesh = _ep_mesh()
    wire = comm.MoEWire(axis="ep", n_slices=2, chips=4,
                        config=comm.MoEDispatchConfig(mode="hier"))

    def fn(buf):
        recv = wire.dispatch(buf)
        assert recv.shape == (e // WORLD, WORLD * cap, d)
        return wire.combine(recv)

    out = np.asarray(shard_map(fn, mesh=mesh, in_specs=(P("ep"),),
                               out_specs=P("ep"))(jnp.asarray(x)))
    np.testing.assert_array_equal(out, x)


def test_moe_wire_rejects_indivisible_experts():
    wire = comm.MoEWire(axis="ep", n_slices=2, chips=4,
                        config=comm.MoEDispatchConfig())
    with pytest.raises(ValueError, match="not divisible by ep"):
        wire.dispatch(jnp.zeros((6, 2, 2)))  # 6 experts on 8 chips


def test_moe_leg_bytes_ratio():
    """The bench's acceptance arithmetic: hier+int8 moves ~4x (>= 3x)
    fewer cross-slice bytes than the dense leg, per leg."""
    blk, s, c = 5 * 4, 2, 4  # cap*d elements per destination block
    dense = comm.moe_leg_bytes(blk, 4, s, c, "off")
    int8 = comm.moe_leg_bytes(blk, 4, s, c, "int8")
    assert dense == (s - 1) * c * blk * 4
    assert int8 == (s - 1) * c * blk + (s - 1) * 4
    assert dense / int8 >= 3.0
    assert comm.moe_leg_bytes(blk, 4, 1, 8, "off") == 0  # single slice


# -- the parity gate through a real step -------------------------------------


def _tiny_moe(world: int, n_layers: int = 1):
    """Smallest trainable MoE problem: one block, E=2*world experts."""
    import optax
    from flax.core import meta
    from edl_tpu.train.state import TrainState

    vocab, seq = 16, 8
    rng = np.random.default_rng(5)
    toks = rng.integers(0, vocab, size=(2 * world, seq)).astype(np.int32)
    cfg = tfm.TransformerConfig(vocab_size=vocab, d_model=16, n_heads=2,
                                n_layers=n_layers, d_ff=32, max_len=seq,
                                dtype=jnp.float32, moe=True,
                                n_experts=2 * world, moe_top_k=2)
    model = tfm.Transformer(cfg)
    variables = meta.unbox(model.init(jax.random.PRNGKey(0),
                                      jnp.asarray(toks), train=False))
    state = TrainState.create(apply_fn=model.apply,
                              params=variables["params"],
                              tx=optax.sgd(0.3, momentum=0.9))

    def loss_factory(wire):
        wired = tfm.Transformer(dataclasses.replace(cfg, moe_wire=wire))
        return functools.partial(tfm.lm_loss_fn,
                                 aux_weight=cfg.moe_aux_weight,
                                 apply_fn=wired.apply)

    return loss_factory, state, {"tokens": toks}


def test_moe_parity_gate_hier_bitwise_and_int8_enveloped():
    """The r21 gate on the dispatch wire: hier/off == flat/off bitwise
    through 2 full training steps on the emulated 2x4 world; the int8
    leg holds the loss envelope."""
    loss_factory, state, batch = _tiny_moe(WORLD)
    topo = mesh_lib.SliceTopology(2, 4)
    mesh = _ep_mesh(topo)
    gate = comm.moe_parity_gate(
        loss_factory, state, batch, mesh=mesh, topology=topo,
        comm_config=comm.CommConfig(bucket_mb=0.25),
        moe_config=comm.MoEDispatchConfig(mode="hier", compress="int8"),
        steps=2, envelope=0.2)
    assert gate["bitwise_hier"] is True
    assert gate["hier_loss_delta"] == 0.0
    assert gate["loss_envelope_ok"], gate
    assert gate["ok"]


def test_moe_step_stats_counter_and_span(monkeypatch):
    """The obs satellite: `step.moe_dispatch` spans every dispatch with
    the wire accounting, `step_moe_dcn_bytes` advances by the static
    per-step bytes, and stats() carries the bench columns."""
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import trace

    calls = []

    @contextlib.contextmanager
    def fake_span(name, parent=None, attrs=None):
        calls.append((name, attrs))
        yield None

    monkeypatch.setattr(trace, "enabled", lambda: True)
    monkeypatch.setattr(trace, "span", fake_span)
    loss_factory, state, batch = _tiny_moe(WORLD)
    topo = mesh_lib.SliceTopology(2, 4)
    mesh = _ep_mesh(topo)
    step = comm.make_moe_comm_step(
        loss_factory, mesh=mesh, topology=topo, donate=False,
        config=comm.CommConfig(bucket_mb=0.25),
        moe_config=comm.MoEDispatchConfig(mode="hier", compress="int8"))
    counter = obs_metrics.registry().counter("step_moe_dcn_bytes")
    before = counter.value
    placed = mesh_lib.shard_batch(mesh, batch, batch_axes=("ep",))
    rep = lambda t: jax.device_put(  # noqa: E731
        t, NamedSharding(mesh, P()))
    s = jax.tree.map(rep, state)
    s, metrics = step(s, placed)
    s, metrics = step(s, placed)
    assert "loss" in metrics and "moe_dropped" in metrics

    stats = step.stats()
    assert stats["moe_dispatch"] == "hier"
    assert stats["moe_compress"] == "int8"
    # one layer = dispatch + combine legs
    assert stats["moe_dispatch_legs"] == 2
    assert stats["moe_dcn_bytes_per_step"] > 0
    assert stats["moe_dispatch_overlap_pct"] == 50.0
    assert counter.value - before \
        == 2 * stats["moe_dcn_bytes_per_step"]

    moe_spans = [(n, a) for n, a in calls if n == "step.moe_dispatch"]
    assert len(moe_spans) == 2
    assert moe_spans[-1][1]["mode"] == "hier"
    assert moe_spans[-1][1]["compress"] == "int8"
    assert moe_spans[-1][1]["moe_dcn_bytes"] \
        == stats["moe_dcn_bytes_per_step"]

    # byte accounting vs the flat baseline: >= 3x fewer DCN bytes
    flat = comm.make_moe_comm_step(
        loss_factory, mesh=mesh, topology=topo, donate=False,
        config=comm.CommConfig(bucket_mb=0.25),
        moe_config=comm.MoEDispatchConfig(mode="flat"))
    s2 = jax.tree.map(rep, state)
    flat(s2, placed)
    assert flat.moe_dcn_bytes_per_step() \
        >= 3 * stats["moe_dcn_bytes_per_step"]
