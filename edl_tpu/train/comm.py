"""DCN-aware gradient path: bucketed, hierarchical, optionally
compressed dp reductions with comm/compute overlap.

The plain jitted step (train/step.py) leaves the gradient allreduce to
XLA's SPMD partitioner: one dense, unoverlapped reduction per parameter
tensor, which on a hybrid ICI×DCN world (parallel/mesh.make_hybrid_mesh)
ships every gradient byte across the slow cross-slice edge exactly as it
falls out of backward. This module is the manual-collective variant the
reference exposed only as opaque fleet flags (`DGCMomentum`,
`use_hierarchical_allreduce` — SURVEY §2.3, train_with_fleet.py:93-112):

- **Bucketing**: gradient leaves are packed, in deterministic tree
  order, into size-bounded flat buckets (one concat buffer per dtype
  group, `CommConfig.bucket_mb`). Each bucket's reduction is an
  INDEPENDENT collective op, so XLA's scheduler can launch bucket i's
  reduction while bucket i+1's producers are still computing — the
  comm/compute overlap the single fused-graph reduction can never have.
  (The reduction itself is elementwise, so bucketing is numerics-free:
  psum(concat(g)) == concat(psum(g)) bitwise.)

- **Hierarchical decomposition**: on a multi-slice topology each
  bucket's dp-reduction becomes dense ICI reduce-scatter within the
  slice -> the cross-slice DCN leg on 1/C of the bytes per chip -> ICI
  all-gather. Only the middle leg crosses DCN, and every chip in a
  slice carries a disjoint 1/C of it.

- **Compressed DCN leg** (`CommConfig.compress`): the cross-slice hop
  optionally ships top-k (values, int32 indices) pairs — the
  `dgc.sparse_psum` wire, here with a persistent error-feedback
  residual so dropped mass is re-contributed on later steps, never
  lost — or int8 values with one per-chip fp32 scale
  (`ops.pack.pack_int8`; Pallas on TPU). ICI legs stay dense and
  bitwise.

Everything sits behind a loss-parity gate (`loss_parity_gate`, the
`smoke` CLI, tests/test_comm_overlap.py): the bucketed-dense path must
be BITWISE-equal to the jit path on the dryrun worlds before the bench
reports its numbers, and compressed paths must hold a pinned loss
envelope on the CNN + transformer convergence smokes.

Scope: the manual path owns dp-only meshes (every other axis size 1 —
dp gradients are the cross-slice traffic ROADMAP 4 names); fsdp/tp
worlds keep the XLA-partitioned step. Power-of-two dp worlds keep the
bitwise guarantee exactly (1/W gradient scaling is then exact); other
world sizes hold it to float tolerance.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.parallel import mesh as mesh_lib
from edl_tpu.parallel.compat import shard_map
from edl_tpu.utils.config import field, from_env, given
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.train.comm")

COMPRESS_MODES = ("off", "topk", "int8")
DEFAULT_BUCKET_MB = 4.0


@dataclass(frozen=True)
class CommConfig:
    """Knobs of the manual gradient path; an entry point builds it with
    `from_flags`.

    bucket_mb: target bucket payload in MiB; a leaf larger than the
      target gets its own bucket. 0 = no bucketing asked for: a
      compressed wire alone then buckets at DEFAULT_BUCKET_MB
      (`target_mb`), and with the wire 'off' too nothing asks for the
      manual step (`asked`) — the XLA-partitioned step stays.
    compress: DCN-leg wire format — 'off' (dense), 'topk'
      (values+indices, error feedback), 'int8' (per-chip scale, error
      feedback).
    topk_frac: fraction of each chip's DCN shard shipped under 'topk'.
    min_compress_elems: shards smaller than this stay dense (index/scale
      overhead would exceed the payload).
    """

    bucket_mb: float = field(0.0, env="EDL_TPU_COMM_BUCKET_MB")
    compress: str = field("off", env="EDL_TPU_DCN_COMPRESS")
    topk_frac: float = 0.01
    min_compress_elems: int = 1024

    def __post_init__(self):
        if self.compress not in COMPRESS_MODES:
            raise ValueError(
                f"compress must be one of {COMPRESS_MODES}, "
                f"got {self.compress!r}")
        if self.bucket_mb < 0:
            raise ValueError(f"bucket_mb must be >= 0, got {self.bucket_mb}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(
                f"topk_frac must be in (0, 1], got {self.topk_frac}")

    @classmethod
    def from_flags(cls, **flags) -> "CommConfig | None":
        """The manual step that the flags (a field each; None = not
        given) or, behind them, the environment names the fields are
        bound to ask for; None where nothing does."""
        cfg = from_env(cls, **given(**flags))
        return cfg if cfg.asked else None

    @property
    def asked(self) -> bool:
        return self.compress != "off" or self.bucket_mb > 0

    @property
    def target_mb(self) -> float:
        return self.bucket_mb or DEFAULT_BUCKET_MB


# -- bucket planning (host-side, static) ------------------------------------


@dataclass(frozen=True)
class _Slot:
    """One gradient leaf's home inside a bucket buffer."""

    leaf: int            # index into the tree-flatten order
    offset: int          # start inside the bucket's flat buffer
    size: int
    shape: tuple


@dataclass(frozen=True)
class _Bucket:
    dtype: Any
    slots: tuple[_Slot, ...]
    size: int            # payload elements (sum of slot sizes)
    padded: int          # payload + pad, a multiple of align


@dataclass(frozen=True)
class BucketPlan:
    """Static partition of a gradient tree into reduction buckets.

    Deterministic in (tree structure, leaf shapes/dtypes, bucket_mb,
    align): the same params always produce the same wire layout — the
    seeded-exact contract the parity tests rely on.
    """

    buckets: tuple[_Bucket, ...]
    treedef: Any
    n_leaves: int
    align: int

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def padded_elems(self) -> int:
        return sum(b.padded for b in self.buckets)


def plan_buckets(params: Any, bucket_mb: float, align: int) -> BucketPlan:
    """Greedy, tree-order bucket partition of a param/grad pytree.

    Leaves are grouped by dtype (one flat buffer cannot mix dtypes
    without a cast that would break bitwise parity), then packed in
    flatten order into buckets of at most ``bucket_mb`` MiB payload —
    an oversized leaf gets a bucket of its own, never split. Each
    bucket is padded up to a multiple of ``align`` (the dp world size,
    so reduce-scatter shards stay integral for every slice factor).
    """
    leaves, treedef = jax.tree.flatten(params)
    budget = max(1, int(bucket_mb * (1 << 20)))
    by_dtype: dict[Any, list[tuple[int, Any]]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype
                            if not hasattr(leaf, "dtype") else leaf.dtype,
                            []).append((i, leaf))
    buckets: list[_Bucket] = []
    for dtype in sorted(by_dtype, key=str):
        pending: list[_Slot] = []
        pend_bytes = 0
        itemsize = np.dtype(dtype).itemsize

        def flush():
            nonlocal pending, pend_bytes
            if not pending:
                return
            size = sum(s.size for s in pending)
            padded = -(-size // align) * align
            buckets.append(_Bucket(dtype=dtype, slots=tuple(pending),
                                   size=size, padded=padded))
            pending, pend_bytes = [], 0

        offset = 0
        for i, leaf in by_dtype[dtype]:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            if pending and pend_bytes + size * itemsize > budget:
                flush()
                offset = 0
            pending.append(_Slot(leaf=i, offset=offset, size=size,
                                 shape=tuple(leaf.shape)))
            offset += size
            pend_bytes += size * itemsize
            if pend_bytes >= budget:
                flush()
                offset = 0
        flush()
    return BucketPlan(buckets=tuple(buckets), treedef=treedef,
                      n_leaves=len(leaves), align=align)


def pack_buckets(grads: Any, plan: BucketPlan) -> list[jnp.ndarray]:
    """Gradient tree -> list of flat padded bucket buffers."""
    leaves = jax.tree.leaves(grads)
    out = []
    for b in plan.buckets:
        parts = [leaves[s.leaf].reshape(-1) for s in b.slots]
        if b.padded > b.size:
            parts.append(jnp.zeros((b.padded - b.size,), b.dtype))
        out.append(jnp.concatenate(parts) if len(parts) > 1
                   else parts[0])
    return out


def unpack_buckets(buffers: list[jnp.ndarray], plan: BucketPlan) -> Any:
    """Inverse of :func:`pack_buckets` (padding discarded)."""
    leaves: list[Any] = [None] * plan.n_leaves
    for buf, b in zip(buffers, plan.buckets):
        for s in b.slots:
            leaves[s.leaf] = lax.slice(buf, (s.offset,),
                                       (s.offset + s.size,)
                                       ).reshape(s.shape)
    return jax.tree.unflatten(plan.treedef, leaves)


# -- wire accounting (static per plan) --------------------------------------


def dcn_bytes_per_step(plan: BucketPlan, config: CommConfig,
                       n_slices: int, chips_per_slice: int) -> int:
    """Bytes ONE chip contributes to the cross-slice leg per step.

    The canonical regression metric (payload actually crossing DCN;
    fabric-level duplication — ring passes, headers — is topology
    noise this deliberately excludes). Dense: the chip's reduce-scatter
    shard at native width. topk: k * (value + int32 index). int8: one
    byte per element + the fp32 scale. Single-slice worlds cross no
    DCN at all and report 0.
    """
    if n_slices <= 1:
        return 0
    total = 0
    for b in plan.buckets:
        total += _leg_bytes(b.padded // chips_per_slice,
                            np.dtype(b.dtype).itemsize, config)
    return total


def _leg_bytes(m: int, itemsize: int, config: CommConfig) -> int:
    """Cross-slice bytes one chip sends for an m-element shard."""
    if config.compress == "off" or m < config.min_compress_elems:
        return m * itemsize
    if config.compress == "topk":
        k = _topk_k(m, config.topk_frac)
        return k * (itemsize + 4)
    return m * 1 + 4  # int8 payload + fp32 scale


def _topk_k(m: int, frac: float) -> int:
    return max(1, int(round(m * frac)))


# -- the reduction (inside shard_map) ---------------------------------------


def _cross_dense(shard, axis, groups):
    return lax.psum(shard, axis, axis_index_groups=groups)


def _cross_topk(shard, resid, axis, groups, k):
    """Top-k values+indices over the DCN edge with error feedback.

    Every chip in the cross group contributes its k largest-|.| entries
    of (shard + residual); the gathered (S, k) pairs scatter-add into a
    dense result identical across the group. Unsent mass stays in the
    residual — re-contributed later, never lost (Lin et al.'s DGC
    invariant, applied to the hierarchical leg instead of the whole
    gradient)."""
    u = shard + resid
    _, idx = lax.top_k(jnp.abs(u), k)
    vals = u[idx]
    all_vals = lax.all_gather(vals, axis, axis_index_groups=groups)
    all_idx = lax.all_gather(idx, axis, axis_index_groups=groups)
    dense = jnp.zeros_like(u).at[all_idx.reshape(-1)].add(
        all_vals.reshape(-1))
    sent = jnp.zeros_like(u).at[idx].add(vals)
    return dense, u - sent


def _cross_int8(shard, resid, axis, groups):
    """int8 DCN edge: per-chip symmetric scale, error feedback keeps
    the quantization error local and re-contributed. Rides the shared
    gather wire (ops/pack.all_gather_int8) — same codec as the DGC
    value wire and the MoE dispatch wire."""
    from edl_tpu.ops.pack import all_gather_int8
    u = shard + resid
    gathered, local = all_gather_int8(u, axis, axis_index_groups=groups)
    dense = jnp.sum(gathered.astype(u.dtype), axis=0)
    return dense, u - local.astype(u.dtype)


def _reduce_bucket(buf, resid, *, axis: str, n_slices: int, chips: int,
                   config: CommConfig):
    """One bucket's dp reduction. Returns (reduced full bucket, new
    residual shard) — residual is a zero-width array when dense."""
    if n_slices <= 1:
        # No DCN edge: one dense allreduce — the exact op XLA's
        # partitioner emits, so the flat bucketed path is bitwise with
        # the jit path by construction.
        return lax.psum(buf, axis), resid
    intra, cross = mesh_lib.dp_comm_groups(n_slices, chips)
    if chips > 1:
        shard = lax.psum_scatter(buf, axis, scatter_dimension=0,
                                 axis_index_groups=intra, tiled=True)
    else:
        shard = buf
    m = shard.shape[0]
    if config.compress == "off" or m < config.min_compress_elems \
            or not jnp.issubdtype(shard.dtype, jnp.floating):
        out = _cross_dense(shard, axis, cross)
    elif config.compress == "topk":
        out, resid = _cross_topk(shard, resid, axis, cross,
                                 _topk_k(m, config.topk_frac))
    else:
        out, resid = _cross_int8(shard, resid, axis, cross)
    if chips > 1:
        out = lax.all_gather(out, axis, axis_index_groups=intra,
                             tiled=True)
    return out, resid


def _needs_residual(bucket: _Bucket, chips: int, n_slices: int,
                    config: CommConfig) -> bool:
    return (config.compress != "off" and n_slices > 1
            and bucket.padded // chips >= config.min_compress_elems
            and jnp.issubdtype(jnp.dtype(bucket.dtype), jnp.floating))


# -- the step ----------------------------------------------------------------


def _validate_dp_mesh(mesh) -> str:
    """The manual path owns dp-only meshes; return the dp axis name."""
    if "dp" not in mesh.axis_names:
        raise ValueError(
            f"comm step needs a dp axis; mesh axes {mesh.axis_names}")
    for name in mesh.axis_names:
        if name != "dp" and mesh.shape[name] != 1:
            raise ValueError(
                "comm step owns dp-only meshes (dp gradients are the "
                f"cross-slice traffic); axis {name!r} has size "
                f"{mesh.shape[name]} — keep the XLA-partitioned step "
                "for fsdp/tp worlds")
    return "dp"


def _validate_ep_mesh(mesh) -> str:
    """The manual MoE path owns ep-only meshes; return the axis name."""
    if "ep" not in mesh.axis_names:
        raise ValueError(
            f"MoE comm step needs an ep axis; mesh axes "
            f"{mesh.axis_names}")
    for name in mesh.axis_names:
        if name != "ep" and mesh.shape[name] != 1:
            raise ValueError(
                "MoE comm step owns ep-only meshes (token dispatch is "
                f"the cross-slice traffic); axis {name!r} has size "
                f"{mesh.shape[name]} — keep the XLA-partitioned step "
                "for mixed meshes")
    return "ep"


class CommTrainStep:
    """``(state, batch) -> (state, metrics)`` with the manual bucketed
    gradient path. Drop-in for TrainLoop; the error-feedback residuals
    ride a closure cell (they are transient comm state, deliberately
    not checkpointed — a restart re-contributes at most one step's
    dropped mass late).

    Built lazily: the bucket plan needs real leaf shapes, so the first
    call plans, initializes residuals and jits; later calls dispatch.

    loss_fn runs INSIDE the manual region: it must be mesh-free — no
    `with_sharding_constraint` / nested shard_map over the same mesh
    (build the model with mesh=None; under shard_map each shard
    computes exactly one chip's backward, so constraints are
    meaningless there and jax rejects them on manual axes).
    """

    def __init__(self, loss_fn: Callable, *, mesh, config: CommConfig,
                 topology=None, donate: bool = True,
                 batch_axes: tuple[str, ...] | None = None):
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.config = config
        self.axis = self._validate_mesh(mesh)
        self.world = int(mesh.shape[self.axis])
        topology = topology or mesh_lib.SliceTopology(1, self.world)
        if self.world % topology.n_slices:
            raise ValueError(
                f"dp={self.world} not divisible by n_slices="
                f"{topology.n_slices}")
        self.topology = topology
        # flat world + compression: the whole dp axis IS the slow edge
        # (every chip is its own slice) — how CPU worlds exercise the
        # compressed wire without emulated slices, and how a
        # single-chip-per-slice fleet degenerates.
        if config.compress != "off" and not topology.is_multi_slice:
            self.n_slices, self.chips = self.world, 1
        else:
            self.n_slices = topology.n_slices
            self.chips = self.world // topology.n_slices
        self.donate = donate
        self.batch_axes = batch_axes
        self.plan: BucketPlan | None = None
        self._jitted = None
        self._comm = None
        self.steps = 0
        self._bytes_counter = None
        try:
            from edl_tpu.obs import metrics as obs_metrics
            self._bytes_counter = obs_metrics.registry().counter(
                "step_dcn_bytes",
                help="bytes this process contributed to cross-slice "
                     "(DCN) gradient legs")
        except Exception:  # noqa: BLE001 — observability is optional
            pass

    def _validate_mesh(self, mesh) -> str:
        return _validate_dp_mesh(mesh)

    # -- static accounting (bench/obs surface) ------------------------------

    def dcn_bytes_per_step(self) -> int:
        """Per-chip cross-slice payload bytes each step (0 until the
        first call plans the buckets; 0 on single-slice topologies
        unless compression treats the flat dp axis as the slow edge)."""
        if self.plan is None:
            return 0
        return dcn_bytes_per_step(
            self.plan, self.config,
            n_slices=self.n_slices,
            chips_per_slice=self.chips)

    def dcn_overlap_pct(self) -> float:
        """Share of cross-slice bytes whose reduction can be in flight
        before the LAST bucket's gradients exist — the schedulable
        overlap the bucketed decomposition exposes (buckets fill in
        backward order; every bucket but the final one is dispatchable
        under remaining compute). A SCHEDULE property, not a
        measurement: the CPU harness has no DCN to overlap — on
        hardware, read the profiler. 0 for a single fused bucket."""
        if self.plan is None or self.plan.n_buckets <= 1 \
                or self.n_slices <= 1:
            return 0.0
        per_bucket = [
            _leg_bytes(b.padded // self.chips,
                       np.dtype(b.dtype).itemsize, self.config)
            for b in self.plan.buckets]
        total = sum(per_bucket)
        if total <= 0:
            return 0.0
        return round(100.0 * (total - per_bucket[-1]) / total, 2)

    def stats(self) -> dict:
        return {"comm_buckets": self.plan.n_buckets if self.plan else 0,
                "comm_bucket_mb": self.config.target_mb,
                "dcn_compress": self.config.compress,
                "dcn_bytes_per_step": self.dcn_bytes_per_step(),
                "dcn_overlap_pct": self.dcn_overlap_pct(),
                "comm_steps": self.steps}

    # -- build ---------------------------------------------------------------

    def _residual_init(self):
        res = []
        for b in self.plan.buckets:
            m = b.padded // self.chips if _needs_residual(
                b, self.chips, self.n_slices, self.config) else 0
            res.append(jnp.zeros((self.world, m), b.dtype))
        # one distinct row per axis position (dp_row_sharding
        # generalized to whichever axis this step owns — ep for MoE)
        sharding = NamedSharding(self.mesh, P(self.axis))
        return tuple(jax.device_put(r, sharding) for r in res)

    def _build(self, state, batch):
        self.plan = plan_buckets(state.params, self.config.target_mb,
                                 align=self.world)
        plan, axis, world = self.plan, self.axis, self.world
        n_slices, chips, config = self.n_slices, self.chips, self.config
        loss_fn = self.loss_fn
        inv_w = 1.0 / world  # power-of-two worlds: an EXACT scaling

        def shard_fn(state, batch, comm):
            def compute(p):
                return loss_fn(state, p, batch)

            (loss, aux), grads = jax.value_and_grad(
                compute, has_aux=True)(state.params)
            # local grads are d(local-mean); x inv_w then sum = global
            # mean, matching the jit path's 1/B_global backward seed
            grads = jax.tree.map(lambda g: g * jnp.asarray(inv_w, g.dtype),
                                 grads)
            bufs = pack_buckets(grads, plan)
            out, new_comm = [], []
            for buf, resid in zip(bufs, comm):
                r, e = _reduce_bucket(buf, resid.reshape(-1),
                                      axis=axis, n_slices=n_slices,
                                      chips=chips, config=config)
                out.append(r)
                new_comm.append(e.reshape(1, -1))
            grads = unpack_buckets(out, plan)
            loss = lax.psum(loss * inv_w, axis)
            # aux (metrics + BN batch_stats) is per-shard under
            # shard_map; average it so the replicated out_spec is
            # truthful. Global-batch variance != mean-of-shard
            # variances — a documented delta of the manual path, inside
            # the smoke's loss envelope.
            aux = jax.tree.map(
                lambda a: lax.pmean(a, axis)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)
                else a, aux)
            return loss, aux, grads, tuple(new_comm)

        # pytree-PREFIX specs: state/grads/aux replicated, batch and
        # residuals sharded over dp on dim 0
        mapped = shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(P(), P(self.axis), P(self.axis)),
            out_specs=(P(), P(), P(), P(self.axis)))

        def step(state, batch, comm):
            loss, aux, grads, comm = mapped(state, batch, comm)
            new_stats = aux.pop("batch_stats", None)
            if new_stats is not None:
                state = state.apply_gradients(grads=grads,
                                              batch_stats=new_stats)
            else:
                state = state.apply_gradients(grads=grads)
            return state, {"loss": loss, **aux}, comm

        donate = (0, 2) if self.donate else ()
        self._jitted = jax.jit(step, donate_argnums=donate)
        self._comm = self._residual_init()
        log.info(
            "comm step: %d buckets (%.1f MiB target, align %d), "
            "%dx%d topology, compress=%s, dcn_bytes/step=%d, "
            "schedulable overlap %.1f%%", plan.n_buckets,
            config.target_mb, world, self.n_slices, self.chips,
            config.compress, self.dcn_bytes_per_step(),
            self.dcn_overlap_pct())

    # -- dispatch ------------------------------------------------------------

    def __call__(self, state, batch):
        if self._jitted is None:
            self._build(state, batch)
        from edl_tpu.obs import trace
        if trace.enabled():
            with trace.span("step.dcn_reduce",
                            attrs={"buckets": self.plan.n_buckets,
                                   "compress": self.config.compress,
                                   "dcn_bytes":
                                       self.dcn_bytes_per_step()}):
                state, metrics, self._comm = self._jitted(
                    state, batch, self._comm)
        else:
            state, metrics, self._comm = self._jitted(state, batch,
                                                      self._comm)
        self.steps += 1
        if self._bytes_counter is not None:
            self._bytes_counter.inc(self.dcn_bytes_per_step())
        return state, metrics


def make_comm_train_step(loss_fn: Callable, *, mesh,
                         config: CommConfig | None = None,
                         topology=None, donate: bool = True
                         ) -> CommTrainStep:
    """Build the manual-collective step. Same ``loss_fn(state, params,
    batch) -> (loss, aux)`` contract as `make_train_step`; returns a
    TrainLoop-compatible ``step(state, batch)`` callable carrying its
    bucket plan and wire accounting (`.stats()`)."""
    return CommTrainStep(loss_fn, mesh=mesh,
                         config=config or CommConfig(),
                         topology=topology, donate=donate)


# -- MoE: hierarchical all-to-all dispatch -----------------------------------
#
# The expert-parallel analogue of the bucketed gradient path above: an
# MoE layer's hot collective is the token all-to-all (dispatch to the
# expert owners, combine back), and on a hybrid topology it decomposes
# the same way the dp reduction does — an ICI leg inside the slice
# (tokens reach their slice's E/S co-resident experts without touching
# DCN) and a cross-slice DCN leg carrying only the OVERFLOW tokens
# routed to another slice's experts, optionally int8 on the wire
# (ops/pack.all_to_all_int8 — the same codec as the gradient legs).
# The decomposition is a pure permutation: uncompressed it is BITWISE
# identical to the single flat collective (moe_parity_gate pins this),
# and the int8 leg sits behind the same loss-envelope + convergence
# discipline as the gradient wire.

MOE_DISPATCH_MODES = ("flat", "hier")
MOE_COMPRESS_MODES = ("off", "int8")


@dataclass(frozen=True)
class MoEDispatchConfig:
    """Knobs of the manual MoE dispatch path, bound to their
    environment names as `CommConfig`'s are.

    mode: 'flat' (one all-to-all over the whole ep axis — the single-
      collective baseline) or 'hier' (ICI leg + DCN overflow leg).
    compress: DCN-leg wire format — 'off' (dense, bitwise with flat)
      or 'int8' (per-destination-block symmetric scale). int8 requires
      mode='hier': only the decomposed path has a separate DCN leg to
      compress.
    """

    mode: str = field("hier", env="EDL_TPU_MOE_DISPATCH")
    compress: str = field("off", env="EDL_TPU_MOE_COMPRESS")

    def __post_init__(self):
        if self.mode not in MOE_DISPATCH_MODES:
            raise ValueError(
                f"mode must be one of {MOE_DISPATCH_MODES}, "
                f"got {self.mode!r}")
        if self.compress not in MOE_COMPRESS_MODES:
            raise ValueError(
                f"compress must be one of {MOE_COMPRESS_MODES}, "
                f"got {self.compress!r}")
        if self.compress != "off" and self.mode != "hier":
            raise ValueError(
                "compress needs mode='hier' — the flat single "
                "collective has no separate DCN leg to compress")


def moe_all_to_all(x, *, axis: str, n_slices: int, chips: int,
                   mode: str = "hier", compress: str = "off"):
    """Destination-major block transport over the ep axis.

    ``x`` is (W, ...) on every chip: block ``x[w]`` is this chip's
    payload bound for chip ``w`` (W = n_slices * chips, slice-major).
    Returns the source-major received buffer of the same shape.

    'flat': one ``lax.all_to_all`` over the whole axis. 'hier': the
    two-level decomposition — an ICI all-to-all within each slice
    delivers every block to the chip IN ITS OWN SLICE holding the
    destination's intra-slice position, then a cross-slice all-to-all
    over the stride-C columns (mesh.ep_comm_groups) carries the
    off-slice blocks over DCN. A pure permutation: bitwise identical
    to 'flat' when uncompressed. compress='int8' quantizes only the
    DCN leg (per-destination-slice symmetric scales); the slice-local
    blocks never leave ICI and stay exact.
    """
    w = n_slices * chips
    if x.shape[0] != w:
        raise ValueError(
            f"dest-major dim {x.shape[0]} != world {n_slices}x{chips}")
    if mode == "flat" or n_slices <= 1:
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    intra, cross = mesh_lib.ep_comm_groups(n_slices, chips)
    b = x.reshape((n_slices, chips) + x.shape[1:])
    if chips > 1:
        b = lax.all_to_all(b, axis, split_axis=1, concat_axis=1,
                           tiled=True, axis_index_groups=intra)
    if compress == "int8" and jnp.issubdtype(b.dtype, jnp.floating):
        from edl_tpu.ops.pack import all_to_all_int8
        b = all_to_all_int8(b, axis,
                            axis_index_groups=cross).astype(x.dtype)
    else:
        b = lax.all_to_all(b, axis, split_axis=0, concat_axis=0,
                           tiled=True, axis_index_groups=cross)
    return b.reshape(x.shape)


def moe_leg_bytes(block_elems: int, itemsize: int, n_slices: int,
                  chips: int, compress: str) -> int:
    """Cross-slice bytes ONE chip sends for one dispatch/combine leg
    whose per-destination-chip block has ``block_elems`` elements —
    the same payload-only accounting as :func:`dcn_bytes_per_step`
    (off-slice blocks only; the slice-local blocks ride ICI free)."""
    if n_slices <= 1:
        return 0
    off = (n_slices - 1) * chips * block_elems
    if compress == "int8":
        return off * 1 + (n_slices - 1) * 4  # int8 payload + fp32 scales
    return off * itemsize


@dataclass
class MoEWire:
    """The transport a manual-region MoE layer dispatches through
    (models/transformer.MoEMLP's ``cfg.moe_wire`` contract): buffer
    reshapes + the grouped collectives, with a trace-time ``on_leg``
    hook so the owning step can account wire bytes statically.

    dispatch: (E, cap, d) per-chip dispatch buffer -> (E/W, W*cap, d)
      tokens received for this chip's local experts.
    combine: the inverse — (E/W, W*cap, d) expert outputs back to
      (E, cap, d) at the token owners.
    local_slice: (E, ...) replicated table -> this chip's (E/W, ...)
      expert rows (the in-region view of what the checkpoint stores
      ep-sharded).
    """

    axis: str
    n_slices: int
    chips: int
    config: MoEDispatchConfig
    on_leg: Callable | None = None

    @property
    def world(self) -> int:
        return self.n_slices * self.chips

    def _transport(self, x):
        if self.on_leg is not None:
            self.on_leg(tuple(int(v) for v in x.shape),
                        np.dtype(x.dtype).itemsize)
        return moe_all_to_all(x, axis=self.axis,
                              n_slices=self.n_slices, chips=self.chips,
                              mode=self.config.mode,
                              compress=self.config.compress)

    def dispatch(self, buf):
        e, cap, d = buf.shape
        w = self.world
        if e % w:
            raise ValueError(f"n_experts={e} not divisible by ep "
                             f"world {w}")
        el = e // w
        r = self._transport(buf.reshape(w, el, cap, d))
        return r.transpose(1, 0, 2, 3).reshape(el, w * cap, d)

    def combine(self, out):
        el, wcap, d = out.shape
        w = self.world
        cap = wcap // w
        r = self._transport(
            out.reshape(el, w, cap, d).transpose(1, 0, 2, 3))
        return r.reshape(w * el, cap, d)

    def local_slice(self, table):
        el = table.shape[0] // self.world
        i = lax.axis_index(self.axis)
        return lax.dynamic_slice_in_dim(table, i * el, el, axis=0)


class MoECommStep(CommTrainStep):
    """``(state, batch) -> (state, metrics)`` for an expert-parallel
    transformer over an ep-only mesh: the bucketed gradient reduction
    of :class:`CommTrainStep` (over ep — each chip's local-mean grads
    x 1/W then psum; an expert table's grad is nonzero only on its
    owner chip, so the same reduction assembles every expert exactly
    once) plus the hierarchical token dispatch injected into the model
    as its ``moe_wire``.

    Built from a loss FACTORY rather than a loss_fn: the factory
    receives the wire and returns a mesh-free ``loss_fn(state, params,
    batch)`` whose MoE layers transport through it (rebind the model
    config's ``moe_wire`` — params are untouched, so states move
    between the jit path and this one freely).
    """

    def __init__(self, loss_factory: Callable, *, mesh,
                 config: CommConfig | None = None,
                 moe_config: MoEDispatchConfig | None = None,
                 topology=None, donate: bool = True):
        moe_config = moe_config or MoEDispatchConfig()
        _validate_ep_mesh(mesh)
        world = int(mesh.shape["ep"])
        topology = topology or mesh_lib.SliceTopology(1, world)
        # flat world + hier dispatch: every chip its own slice (S=W,
        # C=1) — the same degeneration the gradient path uses, so CPU
        # worlds exercise the full DCN wire (incl. int8) without
        # emulated slices.
        if moe_config.mode == "hier" and not topology.is_multi_slice:
            self._moe_slices, self._moe_chips = world, 1
        else:
            self._moe_slices = topology.n_slices
            self._moe_chips = world // topology.n_slices
        self.moe_config = moe_config
        self._legs: list[tuple[tuple, int]] = []
        self.wire = MoEWire(axis="ep", n_slices=self._moe_slices,
                            chips=self._moe_chips, config=moe_config,
                            on_leg=self._record_leg)
        super().__init__(loss_factory(self.wire), mesh=mesh,
                         config=config or CommConfig(),
                         topology=topology, donate=donate)
        self._moe_counter = None
        try:
            from edl_tpu.obs import metrics as obs_metrics
            self._moe_counter = obs_metrics.registry().counter(
                "step_moe_dcn_bytes",
                help="bytes this process contributed to cross-slice "
                     "(DCN) MoE dispatch/combine legs")
        except Exception:  # noqa: BLE001 — observability is optional
            pass

    def _validate_mesh(self, mesh) -> str:
        return _validate_ep_mesh(mesh)

    def _record_leg(self, shape: tuple, itemsize: int):
        # trace-time hook: legs are recorded once, during the first
        # call's trace (self.steps is still 0) — retraces don't
        # double-count
        if self.steps == 0:
            self._legs.append((shape, itemsize))

    # -- static accounting (bench/obs surface) ------------------------------

    def moe_dcn_bytes_per_step(self) -> int:
        """Per-chip cross-slice dispatch+combine payload bytes each
        step (0 until the first call traces the wire)."""
        compress = (self.moe_config.compress
                    if self.moe_config.mode == "hier" else "off")
        total = 0
        for shape, itemsize in self._legs:
            block = int(np.prod(shape[1:])) if len(shape) > 1 else 1
            total += moe_leg_bytes(block, itemsize, self._moe_slices,
                                   self._moe_chips, compress)
        return total

    def moe_dispatch_overlap_pct(self) -> float:
        """Share of cross-slice dispatch bytes whose leg can be in
        flight under other layers' expert compute — every leg except
        the final combine (legs of layer i overlap layer i±1's expert
        FFNs). A SCHEDULE property like dcn_overlap_pct: the CPU
        harness has no DCN to overlap; on hardware, read the
        profiler."""
        n = len(self._legs)
        if n <= 1 or self._moe_slices <= 1:
            return 0.0
        return round(100.0 * (n - 1) / n, 2)

    def stats(self) -> dict:
        out = super().stats()
        out.update({"moe_dispatch": self.moe_config.mode,
                    "moe_compress": self.moe_config.compress,
                    "moe_dispatch_legs": len(self._legs),
                    "moe_dcn_bytes_per_step":
                        self.moe_dcn_bytes_per_step(),
                    "moe_dispatch_overlap_pct":
                        self.moe_dispatch_overlap_pct()})
        return out

    def __call__(self, state, batch):
        from edl_tpu.obs import trace
        if trace.enabled():
            with trace.span("step.moe_dispatch",
                            attrs={"mode": self.moe_config.mode,
                                   "compress": self.moe_config.compress,
                                   "moe_dcn_bytes":
                                       self.moe_dcn_bytes_per_step()}):
                out = super().__call__(state, batch)
        else:
            out = super().__call__(state, batch)
        if self._moe_counter is not None:
            self._moe_counter.inc(self.moe_dcn_bytes_per_step())
        return out


def make_moe_comm_step(loss_factory: Callable, *, mesh,
                       config: CommConfig | None = None,
                       moe_config: MoEDispatchConfig | None = None,
                       topology=None, donate: bool = True
                       ) -> MoECommStep:
    """Build the manual MoE step. ``loss_factory(wire) -> loss_fn``;
    returns a TrainLoop-compatible ``step(state, batch)`` callable
    carrying dispatch wire accounting in ``.stats()``."""
    return MoECommStep(loss_factory, mesh=mesh, config=config,
                       moe_config=moe_config, topology=topology,
                       donate=donate)


# -- the parity gate ---------------------------------------------------------


def tree_bitwise_equal(a, b) -> bool:
    """Bitwise pytree equality (NaNs at equal positions count equal)."""
    ok = [True]

    def cmp(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            ok[0] = False
            return
        if np.issubdtype(x.dtype, np.floating):
            same = (x == y) | (np.isnan(x) & np.isnan(y))
            ok[0] = ok[0] and bool(same.all())
        else:
            ok[0] = ok[0] and bool(np.array_equal(x, y))

    jax.tree.map(cmp, jax.device_get(a), jax.device_get(b))
    return ok[0]


def loss_parity_gate(loss_fn: Callable, state, batch, *, mesh,
                     config: CommConfig, topology=None, steps: int = 3,
                     envelope: float = 5e-3) -> dict:
    """The gate the bench must pass before reporting DCN numbers.

    1. bucketed-DENSE vs the plain jit step: identical params AND loss
       after ``steps`` steps, bitwise (``bitwise_dense``).
    2. if ``config.compress != off``: the compressed path's per-step
       loss stays within ``envelope`` of the jit path's
       (``loss_envelope_ok`` / ``max_loss_delta``).

    Callers hand in a throwaway state (both paths train from it).
    """
    from edl_tpu.train.step import make_train_step

    placed = mesh_lib.shard_batch(mesh, batch)
    rep = lambda t: jax.device_put(  # noqa: E731
        t, NamedSharding(mesh, P()))
    jit_step = make_train_step(loss_fn, donate=False)
    s_jit = jax.tree.map(rep, state)
    jit_losses = []
    for _ in range(steps):
        s_jit, m = jit_step(s_jit, placed)
        jit_losses.append(float(m["loss"]))

    dense = make_comm_train_step(
        loss_fn, mesh=mesh, topology=topology, donate=False,
        config=dataclasses.replace(config, compress="off"))
    s_dense = jax.tree.map(rep, state)
    dense_loss = None
    for _ in range(steps):
        s_dense, m = dense(s_dense, placed)
        dense_loss = float(m["loss"])
    gate = {"bitwise_dense": tree_bitwise_equal(s_jit.params,
                                                s_dense.params)
            and dense_loss == jit_losses[-1],
            # float-tolerance parity of the dense path (what a
            # hierarchically re-associated sum can hold when bitwise
            # cannot)
            "dense_loss_delta": abs(dense_loss - jit_losses[-1]),
            "envelope": envelope, "steps": steps}
    if config.compress != "off":
        comp = make_comm_train_step(loss_fn, mesh=mesh,
                                    topology=topology, donate=False,
                                    config=config)
        s_comp = jax.tree.map(rep, state)
        deltas = []
        for i in range(steps):
            s_comp, m = comp(s_comp, placed)
            deltas.append(abs(float(m["loss"]) - jit_losses[i]))
        gate["max_loss_delta"] = max(deltas)
        gate["loss_envelope_ok"] = max(deltas) <= envelope
    gate["ok"] = bool(gate["bitwise_dense"]
                      and gate.get("loss_envelope_ok", True))
    return gate


# -- convergence-parity smoke (the CI gate) ----------------------------------


def _smoke_cnn(world: int):
    """Tiny BN CNN on separable synthetic images: dense-jit vs topk."""
    import optax

    from edl_tpu.models.resnet import ResNetTiny
    from edl_tpu.train import classification as cls

    rng = np.random.default_rng(7)
    n, hw, classes = 8 * world, 16, 4
    labels = rng.integers(0, classes, size=n).astype(np.int32)
    # class-colored images + noise: learnable in a few dozen steps
    images = (rng.normal(0, 0.3, size=(n, hw, hw, 3))
              + labels[:, None, None, None] / classes).astype(np.float32)
    model = ResNetTiny(num_classes=classes, dtype=jnp.float32)
    state = cls.create_state(model, jax.random.PRNGKey(0),
                             (1, hw, hw, 3), optax.sgd(0.05, momentum=0.9))

    def loss_fn(state, params, batch):
        variables = {"params": params, "batch_stats": state.batch_stats}
        logits, mutated = state.apply_fn(variables, batch["image"],
                                         train=True,
                                         mutable=["batch_stats"])
        targets = cls.smoothed_labels(batch["label"], classes, 0.0)
        loss = cls.soft_cross_entropy(logits, targets)
        return loss, {"batch_stats": mutated["batch_stats"]}

    return loss_fn, state, {"image": images, "label": labels}


def _smoke_transformer(world: int, mesh):
    """Tiny markov-LM transformer: the no-BN, bitwise-testable model."""
    import optax
    from flax.core import meta

    from edl_tpu.models.transformer import (Transformer,
                                            TransformerConfig, lm_loss_fn)
    from edl_tpu.train.state import TrainState

    vocab, seq = 32, 16
    gen = np.random.default_rng(11)
    successors = gen.integers(0, vocab, size=(vocab, 4))
    toks = np.empty((4 * world, seq), np.int32)
    toks[:, 0] = gen.integers(0, vocab, size=4 * world)
    for t in range(1, seq):
        pick = gen.integers(0, 4, size=4 * world)
        toks[:, t] = successors[toks[:, t - 1], pick]
    del mesh  # the comm region is mesh-free: constraints would clash
    # with shard_map's manual axes (see CommTrainStep docstring)
    cfg = TransformerConfig(vocab_size=vocab, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=seq,
                            dtype=jnp.float32, mesh=None)
    model = Transformer(cfg)
    variables = meta.unbox(model.init(jax.random.PRNGKey(0),
                                      jnp.asarray(toks), train=False))
    # momentum-SGD: the optimizer DGC's error-feedback analysis (and
    # the reference's DGCMomentum) is built for — adam's second moment
    # amplifies early sparsification noise and needs a longer horizon
    state = TrainState.create(apply_fn=model.apply,
                              params=variables["params"],
                              tx=optax.sgd(0.5, momentum=0.9))
    return lm_loss_fn, state, {"tokens": toks}


def convergence_smoke(compress: str = "topk", steps: int = 40,
                      envelope: float = 0.25,
                      topology=None) -> dict:
    """CNN + transformer convergence smokes: train the compressed path
    against dense-jit from the same init; both must LEARN (final loss
    below initial) and the compressed run must keep at least
    ``1 - envelope`` of dense's loss improvement (|dense - compressed|
    <= envelope * (initial - dense) — a RELATIVE envelope, so one pin
    serves models whose loss scales differ by 40x). The topk wire runs
    at 1/8 density = exactly the 4x DCN byte reduction the bench
    gates on. Returns the report dict; `smoke` CLI exits nonzero
    unless every gate holds."""
    world = jax.device_count()
    mesh = (mesh_lib.make_hybrid_mesh(mesh_lib.MeshSpec({"dp": -1}),
                                      topology)
            if topology is not None and topology.is_multi_slice
            else mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1})))
    report: dict = {"compress": compress, "steps": steps,
                    "envelope": envelope, "world": world,
                    "n_slices": topology.n_slices if topology else 1}

    def run(name, loss_fn, state, batch):
        placed = mesh_lib.shard_batch(mesh, batch)
        rep = lambda t: jax.device_put(  # noqa: E731
            t, NamedSharding(mesh, P()))
        from edl_tpu.train.step import make_train_step
        jit_step = make_train_step(loss_fn, donate=False)
        comp = make_comm_train_step(
            loss_fn, mesh=mesh, topology=topology, donate=False,
            config=CommConfig(bucket_mb=0.25, compress=compress,
                              topk_frac=0.125, min_compress_elems=64))
        s_a = jax.tree.map(rep, state)
        s_b = jax.tree.map(rep, state)
        first = last_a = last_b = None
        for _ in range(steps):
            s_a, m_a = jit_step(s_a, placed)
            s_b, m_b = comp(s_b, placed)
            if first is None:
                first = float(m_a["loss"])
            last_a, last_b = float(m_a["loss"]), float(m_b["loss"])
        delta = abs(last_a - last_b)
        improvement = max(first - last_a, 1e-9)
        report[name] = {
            "loss_initial": round(first, 4),
            "loss_dense": round(last_a, 4),
            "loss_compressed": round(last_b, 4),
            "delta": round(delta, 5),
            "delta_rel": round(delta / improvement, 5),
            "learned": last_a < first and last_b < first,
            "within_envelope": delta <= envelope * improvement}

    run("cnn", *_smoke_cnn(world))
    run("transformer", *_smoke_transformer(world, mesh))
    report["ok"] = all(report[k]["learned"] and report[k]["within_envelope"]
                      for k in ("cnn", "transformer"))
    return report


# -- MoE dispatch gates -------------------------------------------------------


def moe_parity_gate(loss_factory: Callable, state, batch, *, mesh,
                    moe_config: MoEDispatchConfig | None = None,
                    comm_config: CommConfig | None = None,
                    topology=None, steps: int = 3,
                    envelope: float = 0.1) -> dict:
    """The gate the bench must pass before reporting MoE DCN numbers —
    the r21 discipline applied to the dispatch wire.

    1. hier-UNCOMPRESSED vs the flat single collective: identical
       params AND per-step losses after ``steps`` steps, bitwise
       (``bitwise_hier`` — the decomposition is a pure permutation, so
       anything less is a wiring bug, not float noise).
    2. if ``moe_config.compress != 'off'``: the compressed path's
       per-step loss stays within ``envelope`` of flat's
       (``loss_envelope_ok`` / ``max_loss_delta``). The default is
       wider than the gradient gate's: int8 here rides ACTIVATIONS
       (transient — no error-feedback residual to reclaim rounding),
       so per-step deltas are real quantization noise, a few percent
       of a from-init loss; the convergence smoke's RELATIVE envelope
       is the binding check on whether that noise costs learning.

    Both arms are MoECommStep instances — jit-vs-manual is NOT gated
    here: the manual region routes per CHIP under a local capacity
    while the jit path is dropless, a documented semantic delta
    covered by the convergence smoke's relative envelope.
    Callers hand in a throwaway state (every arm trains from it).
    """
    moe_config = moe_config or MoEDispatchConfig()
    placed = mesh_lib.shard_batch(mesh, batch, batch_axes=("ep",))
    rep = lambda t: jax.device_put(  # noqa: E731
        t, NamedSharding(mesh, P()))

    def run(mcfg):
        step = MoECommStep(loss_factory, mesh=mesh,
                           config=comm_config, moe_config=mcfg,
                           topology=topology, donate=False)
        s = jax.tree.map(rep, state)
        losses = []
        for _ in range(steps):
            s, m = step(s, placed)
            losses.append(float(m["loss"]))
        return s, losses

    s_flat, l_flat = run(MoEDispatchConfig(mode="flat"))
    s_hier, l_hier = run(MoEDispatchConfig(mode="hier"))
    gate = {"bitwise_hier": tree_bitwise_equal(s_flat.params,
                                               s_hier.params)
            and l_flat == l_hier,
            "hier_loss_delta": max(abs(a - b)
                                   for a, b in zip(l_flat, l_hier)),
            "envelope": envelope, "steps": steps}
    if moe_config.compress != "off":
        _, l_comp = run(moe_config)
        deltas = [abs(a - b) for a, b in zip(l_flat, l_comp)]
        gate["max_loss_delta"] = max(deltas)
        gate["loss_envelope_ok"] = max(deltas) <= envelope
    gate["ok"] = bool(gate["bitwise_hier"]
                      and gate.get("loss_envelope_ok", True))
    return gate


def _smoke_moe(world: int):
    """Tiny MoE markov-LM: returns ``(loss_factory, jit_loss_fn,
    state, batch)``. The factory closes over the wire for the manual
    step; the jit loss runs the dropless dispatch (wire=None) on the
    same params."""
    import functools

    import optax
    from flax.core import meta

    from edl_tpu.models.transformer import (Transformer,
                                            TransformerConfig, lm_loss_fn)
    from edl_tpu.train.state import TrainState

    vocab, seq = 32, 16
    gen = np.random.default_rng(23)
    successors = gen.integers(0, vocab, size=(vocab, 4))
    toks = np.empty((4 * world, seq), np.int32)
    toks[:, 0] = gen.integers(0, vocab, size=4 * world)
    for t in range(1, seq):
        pick = gen.integers(0, 4, size=4 * world)
        toks[:, t] = successors[toks[:, t - 1], pick]
    cfg = TransformerConfig(vocab_size=vocab, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=seq,
                            dtype=jnp.float32, mesh=None, moe=True,
                            n_experts=2 * world, moe_top_k=2)
    model = Transformer(cfg)
    variables = meta.unbox(model.init(jax.random.PRNGKey(0),
                                      jnp.asarray(toks), train=False))
    state = TrainState.create(apply_fn=model.apply,
                              params=variables["params"],
                              tx=optax.sgd(0.5, momentum=0.9))

    def loss_factory(wire):
        wired = Transformer(dataclasses.replace(cfg, moe_wire=wire))
        return functools.partial(lm_loss_fn, apply_fn=wired.apply)

    return loss_factory, lm_loss_fn, state, {"tokens": toks}


def moe_convergence_smoke(compress: str = "int8", steps: int = 40,
                          envelope: float = 0.25,
                          topology=None) -> dict:
    """MoE convergence smoke (the ``smoke --moe`` CI gate): train the
    hierarchical+compressed dispatch against the uncompressed manual
    baseline from the same init. Both must LEARN and the compressed
    run must keep at least ``1 - envelope`` of the baseline's loss
    improvement — the relative envelope of `convergence_smoke`, but
    SIGNED: only a compressed run that converges WORSE by more than
    ``envelope * improvement`` fails (at lr high enough to learn in
    40 steps, trajectories diverge chaotically under any per-step
    noise, and int8 dispatch noise can just as well land ahead of the
    baseline — penalizing |delta| would fail runs that beat dense).
    The flat/off MoECommStep is the dense reference so the
    envelope isolates the wire (per-chip routing is identical in both
    arms; the jit path's dropless routing delta is reported as
    ``jit_loss_final`` for the learned check, not gated). Runs the
    bitwise flat-vs-hier parity gate first; a red gate fails the
    smoke regardless of convergence."""
    world = jax.device_count()
    mesh = (mesh_lib.make_hybrid_mesh(mesh_lib.MeshSpec({"ep": -1}),
                                      topology)
            if topology is not None and topology.is_multi_slice
            else mesh_lib.make_mesh(mesh_lib.MeshSpec({"ep": -1})))
    loss_factory, jit_loss, state, batch = _smoke_moe(world)
    placed = mesh_lib.shard_batch(mesh, batch, batch_axes=("ep",))
    rep = lambda t: jax.device_put(  # noqa: E731
        t, NamedSharding(mesh, P()))
    comm_cfg = CommConfig(bucket_mb=0.25)

    gate = moe_parity_gate(
        loss_factory, state, batch, mesh=mesh, topology=topology,
        comm_config=comm_cfg,
        moe_config=MoEDispatchConfig(mode="hier", compress=compress))

    def run(mcfg):
        step = MoECommStep(loss_factory, mesh=mesh, config=comm_cfg,
                           moe_config=mcfg, topology=topology,
                           donate=False)
        s = jax.tree.map(rep, state)
        first = last = None
        for _ in range(steps):
            s, m = step(s, placed)
            if first is None:
                first = float(m["loss"])
            last = float(m["loss"])
        return first, last, step

    first, dense_last, _ = run(MoEDispatchConfig(mode="flat"))
    _, comp_last, comp_step = run(
        MoEDispatchConfig(mode="hier", compress=compress))

    from edl_tpu.train.step import make_train_step
    jit_step = make_train_step(jit_loss, donate=False)
    s_jit = jax.tree.map(rep, state)
    jit_last = None
    for _ in range(steps):
        s_jit, m = jit_step(s_jit, placed)
        jit_last = float(m["loss"])

    delta = comp_last - dense_last  # signed: + = compression cost
    improvement = max(first - dense_last, 1e-9)
    report = {
        "compress": compress, "steps": steps, "envelope": envelope,
        "world": world,
        "n_slices": topology.n_slices if topology else 1,
        "parity_gate": gate,
        "loss_initial": round(first, 4),
        "loss_dense": round(dense_last, 4),
        "loss_compressed": round(comp_last, 4),
        "jit_loss_final": round(jit_last, 4),
        "delta": round(delta, 5),
        "delta_rel": round(delta / improvement, 5),
        "learned": dense_last < first and comp_last < first,
        "within_envelope": delta <= envelope * improvement,
        "moe_dcn_bytes_per_step": comp_step.moe_dcn_bytes_per_step(),
        "moe_dispatch_overlap_pct":
            comp_step.moe_dispatch_overlap_pct()}
    report["ok"] = bool(gate["ok"] and report["learned"]
                        and report["within_envelope"])
    return report


def _main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(prog="edl_tpu.train.comm")
    sub = parser.add_subparsers(dest="cmd", required=True)
    smoke = sub.add_parser(
        "smoke", help="convergence-parity smoke: compressed DCN leg vs "
                      "dense jit on the CNN + transformer tinies")
    smoke.add_argument("--compress", choices=("topk", "int8"),
                       default=None,
                       help="DCN wire format (default: topk; int8 "
                            "with --moe, which has no topk wire)")
    smoke.add_argument("--moe", action="store_true",
                       help="run the MoE dispatch smoke (hier+int8 "
                            "all-to-all vs the flat/off manual "
                            "baseline, bitwise parity gate first) "
                            "instead of the gradient-wire smokes")
    smoke.add_argument("--steps", type=int, default=40)
    smoke.add_argument("--envelope", type=float, default=0.25,
                       help="RELATIVE loss envelope: the compressed "
                            "run must keep >= 1-envelope of dense's "
                            "loss improvement")
    smoke.add_argument("--slices", type=int, default=2,
                       help="emulated slice count (1 = flat dp)")
    args = parser.parse_args(argv)
    world = jax.device_count()
    topo = None
    if args.slices > 1:
        if world % args.slices:
            raise SystemExit(f"{world} devices not divisible by "
                             f"--slices {args.slices}")
        topo = mesh_lib.SliceTopology(args.slices, world // args.slices)
    if args.moe:
        compress = args.compress or "int8"
        if compress == "topk":
            raise SystemExit("--moe supports --compress int8 only "
                             "(token blocks have no sparse wire)")
        report = moe_convergence_smoke(compress=compress,
                                       steps=args.steps,
                                       envelope=args.envelope,
                                       topology=topo)
        print(json.dumps({"moe_smoke": report}))
        return 0 if report["ok"] else 1
    report = convergence_smoke(compress=args.compress or "topk",
                               steps=args.steps,
                               envelope=args.envelope, topology=topo)
    print(json.dumps({"comm_smoke": report}))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    import sys

    sys.exit(_main())
