"""Training step builder: nested-shard_map distribution with the paper's
compressed collectives on every DP wire.

Structure (validated for lowering on 512-device meshes):

    jit
     └─ outer shard_map — MANUAL over (pod, data); AUTO over model
         ├─ grad accumulation scan over microbatches
         │    └─ loss: forward (remat'd superblock scan, GSPMD TP over
         │       'model') + sequence-chunked cross-entropy
         ├─ partition = zero1:  inner shard_map — manualizes 'model'
         │    └─ flat per-dtype buckets → compressed reduce-scatter →
         │       fp32 shard update → compressed all-gather (optim/zero1.py);
         │       a program of its own, apart from the gradient program
         └─ partition = fsdp:   params enter DP-sharded; compressed
              all-gathers run inside the forward scan via block_param_fn,
              their custom-vjp transposes reduce-scatter the gradients
              (optim/fsdp.py); optimizer updates local shards directly

Losslessness: every compressed wire carries an overflow flag; when
``guard_overflow`` the whole state update is masked out on overflow and the
runtime retries the step with compression disabled (runtime/fault_tolerance).

Fused execution (paper §3.4): every DP reduce-scatter receive — the zero1
gradient sync and the FSDP gather's backward — streams remote packed chunks
through the fused decode+reduce kernel into the f32 accumulator
(``policy.fused_decode_reduce``, default on), eliminating the decoded-float
HBM round-trip of decode-then-sum.  Fused and unfused paths are
bit-identical (device-index accumulation order everywhere), so the knob is
purely a performance/accounting choice.  Each compressed wire also records
a trace-time ``WireReport`` (see core/policy.py); tracing a step and
draining ``policy.wire_reports()`` yields the measured wire/HBM accounting
the roofline consumes (``roofline.analysis.summarize_wire_reports``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.policy import CompressionPolicy
from repro.models import transformer
from repro.models.config import ArchConfig
from repro.optim import fsdp as fsdp_lib
from repro.optim import optimizers as opt
from repro.optim import zero1 as zero1_lib
from repro.sched import compile as sched_compile
from repro.sched import executor as sched_executor


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat: bool = True
    loss_chunk: int = 1024
    partition: str = "zero1"  # zero1 | fsdp
    optim: opt.OptimConfig = dataclasses.field(default_factory=opt.OptimConfig)
    policy: CompressionPolicy = dataclasses.field(
        default_factory=CompressionPolicy)
    guard_overflow: bool = True
    fsdp_min_bytes: int = 1 << 20
    # pure-DP mode: replicate params over 'model' and use it as extra data
    # parallelism.  For small archs (d_model ≪ 16×128) TP at model=16 is
    # pathological — activation all-reduces dwarf compute (§Perf); pure DP
    # eliminates TP traffic and syncs grads with ONE compressed two-shot
    # over all 256/512 devices (the paper's collective, at full scale).
    dp_only: bool = False


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def chunked_ce_loss(params, hidden, labels, cfg: ArchConfig, chunk: int):
    """Sequence-chunked cross-entropy: logits are materialized ``chunk``
    positions at a time and rematerialized in backward, bounding the live
    (B, chunk, vocab) fp32 buffer (vocab stays GSPMD-sharded over model)."""
    B, S, D = hidden.shape
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    chunk = min(chunk, S)
    n = S // chunk if S % chunk == 0 else 1
    if S % chunk != 0:
        chunk = S

    @jax.checkpoint
    def piece(h_c, y_c):
        logits = (h_c @ head.T).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    hs = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ys = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    total = jnp.sum(jax.lax.map(lambda a: piece(*a), (hs, ys)))
    return total / (B * S)


# ---------------------------------------------------------------------------
# state construction
# ---------------------------------------------------------------------------

def dp_axes_of(mesh) -> tuple:
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def train_axes_of(mesh, tcfg) -> tuple:
    """The manual (gradient-sync) axes: pod/data, plus 'model' in pure-DP
    mode (where the model axis carries batch, not tensor, parallelism)."""
    names = mesh.axis_names
    axes = ("pod", "data", "model") if tcfg.dp_only else ("pod", "data")
    return tuple(a for a in axes if a in names)


def train_param_specs(cfg, tcfg, mesh):
    """Model-axis param specs (sanitized), or fully-replicated in dp_only."""
    if tcfg.dp_only:
        return jax.tree.map(lambda s: P(*((None,) * len(tuple(s)))),
                            transformer.specs(cfg),
                            is_leaf=lambda x: isinstance(x, P))
    return model_specs(cfg, mesh)


def _flatten_specs(tree_specs):
    return tree_specs


def sanitize_specs(pspecs, params_shape, mesh):
    """Drop sharding entries whose dim does not divide the mesh axes — e.g.
    xlstm gate projections (n_heads=4) on a model=16 mesh stay replicated.
    Keeps the manual-region local-shape arithmetic exact."""
    def f(spec, p):
        entries = list(tuple(spec)) + [None] * (p.ndim - len(tuple(spec)))
        out = []
        for dim, e in enumerate(entries[: p.ndim]):
            if e is None:
                out.append(None)
                continue
            names = (e,) if isinstance(e, str) else tuple(e)
            total = int(np.prod([mesh.shape[a] for a in names]))
            out.append(e if p.shape[dim] % total == 0 else None)
        return P(*out)

    return jax.tree.map(f, pspecs, params_shape,
                        is_leaf=lambda x: isinstance(x, P))


def model_specs(cfg: ArchConfig, mesh):
    """Mesh-sanitized parameter PartitionSpecs."""
    return sanitize_specs(transformer.specs(cfg),
                          transformer.abstract_params(cfg), mesh)


def local_param_struct(cfg: ArchConfig, mesh, pspecs=None):
    """ShapeDtypeStructs of the per-model-shard local parameters."""
    params_shape = transformer.abstract_params(cfg)
    pspecs = pspecs if pspecs is not None else model_specs(cfg, mesh)

    def f(p, s):
        shape = list(p.shape)
        entries = list(tuple(s)) + [None] * (p.ndim - len(tuple(s)))
        for dim, e in enumerate(entries[: p.ndim]):
            if e is None:
                continue
            names = (e,) if isinstance(e, str) else tuple(e)
            shape[dim] //= int(np.prod([mesh.shape[a] for a in names]))
        return jax.ShapeDtypeStruct(tuple(shape), p.dtype)

    return jax.tree.map(f, params_shape, pspecs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def make_train_state_specs(cfg: ArchConfig, tcfg: TrainConfig, mesh):
    """PartitionSpec pytree for the train state (params, opt, step)."""
    pspecs = model_specs(cfg, mesh)
    dp = dp_axes_of(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    n_model = mesh.shape["model"]
    params_shape = transformer.abstract_params(cfg)
    if tcfg.partition == "fsdp":
        plan = plan_fsdp_tree(cfg, tcfg, mesh)
        ospecs = fsdp_opt_specs(params_shape, pspecs, plan, tcfg, dp, n_dp)
        pspecs = fsdp_param_specs(pspecs, plan, dp)
        return {"params": pspecs, "opt": ospecs, "step": P()}
    # zero1: params replicated over the sync axes
    axes = train_axes_of(mesh, tcfg)
    n_sync = int(np.prod([mesh.shape[a] for a in axes]))
    pspecs = train_param_specs(cfg, tcfg, mesh)
    meta = zero1_meta(cfg, n_sync, tcfg, mesh)
    n_inner = 1 if tcfg.dp_only else n_model
    ostruct = zero1_lib.state_struct(tcfg.optim, meta, n_inner)
    ospecs = jax.tree.map(
        lambda s: P(axes, None) if getattr(s, "ndim", 0) == 2 else P(),
        ostruct,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    return {"params": pspecs, "opt": ospecs, "step": P()}


def zero1_meta(cfg: ArchConfig, n_dp: int, tcfg: TrainConfig, mesh):
    """Bucket plan on the LOCAL (per-model-shard) shapes: flattening happens
    inside the fully-manual region where leaves are local."""
    return zero1_lib.plan_buckets(
        local_param_struct(cfg, mesh, train_param_specs(cfg, tcfg, mesh)),
        n_dp, block=tcfg.policy.profile.block)


# -- FSDP planning ----------------------------------------------------------

def plan_fsdp_tree(cfg: ArchConfig, tcfg: TrainConfig, mesh):
    """Per-leaf FSDP dim tree (-1 = replicated), aligned with params."""
    dp = dp_axes_of(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    params_shape = transformer.abstract_params(cfg)
    pspecs = model_specs(cfg, mesh)

    def choose(leaf, spec):
        entries = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
        size = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        if size < tcfg.fsdp_min_bytes:
            return -1
        from repro.core import codec
        if jnp.dtype(leaf.dtype).name not in codec.LAYOUTS:
            return -1
        for d in range(leaf.ndim - 1, 0, -1):  # never dim 0 (scan axis)
            if entries[d] is None and leaf.shape[d] % n_dp == 0:
                return d
        return -1

    return jax.tree.map(choose, params_shape, pspecs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
                        or isinstance(x, P))


def fsdp_param_specs(pspecs, plan, dp):
    """Insert the DP axes into each sharded leaf's PartitionSpec."""
    def upd(spec, dim):
        if dim < 0:
            return spec
        entries = list(tuple(spec))
        entries += [None] * (dim + 1 - len(entries))
        entries[dim] = dp if len(dp) > 1 else dp[0]
        return P(*entries)

    return jax.tree.map(upd, pspecs, plan,
                        is_leaf=lambda x: isinstance(x, P))


def fsdp_local_shapes(params_shape, plan, n_dp: int):
    """ShapeDtypeStructs of the per-device param shards."""
    def f(p, d):
        if d < 0:
            return p
        shape = list(p.shape)
        shape[d] //= n_dp
        return jax.ShapeDtypeStruct(tuple(shape), p.dtype)
    return jax.tree.map(f, params_shape, plan)


def fsdp_opt_specs(params_shape, pspecs, plan, tcfg: TrainConfig, dp, n_dp):
    """Optimizer-state specs for FSDP.

    State leaves are stored globally with a leading DP dim — global shape
    ``(n_dp,) + local_shard_shape`` — so per-shard state (which genuinely
    differs across DP ranks, e.g. adafactor row factors of a sharded dim)
    has a uniform GSPMD-addressable representation.  ``pspecs`` here are
    the ORIGINAL (model-only) specs: the shard's own dims keep their
    model-axis sharding; the plan dim's entry becomes None (it is local).
    """
    dpax = dp if len(dp) > 1 else dp[0]

    def local_entries(p, spec, dim):
        entries = list(tuple(spec)) + [None] * (p.ndim - len(tuple(spec)))
        if dim >= 0:
            entries[dim] = None
        return entries

    def full_spec(p, spec, dim):
        return P(*([dpax] + local_entries(p, spec, dim)))

    full_tree = jax.tree.map(full_spec, params_shape, pspecs, plan)
    if tcfg.optim.name == "adamw":
        return {"m": full_tree, "v": full_tree, "count": P()}

    def af_spec(p, spec, dim):
        ent = local_entries(p, spec, dim)
        lshape = list(p.shape)
        if dim >= 0:
            lshape[dim] //= n_dp
        if opt._factored(tuple(lshape), tcfg.optim.factored_min_dim):
            return {"vr": P(*([dpax] + ent[:-1])),
                    "vc": P(*([dpax] + ent[:-2] + ent[-1:]))}
        return {"v": P(*([dpax] + ent))}

    f = jax.tree.map(af_spec, params_shape, pspecs, plan)
    return {"f": f, "count": P()}


# ---------------------------------------------------------------------------
# state initialization
# ---------------------------------------------------------------------------

def _zero1_opt_specs_inner(meta, ocfg):
    keys = {"adamw": ("master", "m", "v"), "adafactor": ("master", "v")}[
        ocfg.name]
    return {
        "count": P(),
        "buckets": tuple({k: P(None, "model") for k in keys}
                         for _ in meta.dtype_names),
    }


def _zero1_opt_specs_outer(meta, ocfg, dp):
    ax = dp if len(dp) > 1 else dp[0]
    keys = {"adamw": ("master", "m", "v"), "adafactor": ("master", "v")}[
        ocfg.name]
    return {
        "count": P(),
        "buckets": tuple({k: P(ax, None) for k in keys}
                         for _ in meta.dtype_names),
    }


def build_train_state(cfg: ArchConfig, tcfg: TrainConfig, mesh, rng):
    """Initialize a sharded train state on ``mesh``.  Returns (state, specs)."""
    dp = dp_axes_of(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    pspecs = train_param_specs(cfg, tcfg, mesh)
    state_specs = make_train_state_specs(cfg, tcfg, mesh)
    params = transformer.init(rng, cfg)
    params = jax.device_put(
        params,
        jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                     is_leaf=lambda x: isinstance(x, P)))

    if tcfg.partition == "zero1":
        axes = train_axes_of(mesh, tcfg)
        n_sync = int(np.prod([mesh.shape[a] for a in axes]))
        meta = zero1_meta(cfg, n_sync, tcfg, mesh)

        def outer(params):
            idx = zero1_lib._dp_index(tuple(axes))

            def init_local(p, i):
                st = zero1_lib.zero1_init_local(
                    tcfg.optim, meta, p, tuple(axes), dp_index=i)
                return zero1_lib.local_to_global(st)

            if tcfg.dp_only:
                return init_local(params, idx)
            return jax.shard_map(
                init_local, in_specs=(pspecs, P()),
                out_specs=_zero1_opt_specs_inner(meta, tcfg.optim),
                axis_names={"model"}, check_vma=False)(params, idx)

        opt_state = jax.jit(lambda p: jax.shard_map(
            outer, mesh=mesh, in_specs=(P(),),
            out_specs=_zero1_opt_specs_outer(meta, tcfg.optim, axes),
            axis_names=set(axes), check_vma=False)(p))(params)
        state = {"params": params, "opt": opt_state,
                 "step": jnp.zeros((), jnp.int32)}
        return state, state_specs

    # fsdp: shard params per plan, then init optimizer on the local shards
    plan = plan_fsdp_tree(cfg, tcfg, mesh)

    def outer(params):
        idx = zero1_lib._dp_index(tuple(dp))
        local = fsdp_lib.shard_tree_by_plan(plan, params, idx, n_dp)
        ost = opt.init(tcfg.optim, local)
        return local, _opt_global(ost)

    manual_p = _manual_state_specs(state_specs["params"], dp)
    manual_o = _manual_state_specs(state_specs["opt"], dp)
    params_sharded, opt_state = jax.jit(lambda p: jax.shard_map(
        outer, mesh=mesh, in_specs=(P(),),
        out_specs=(manual_p, manual_o),
        axis_names=set(dp), check_vma=False)(p))(params)
    state = {"params": params_sharded, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    return state, state_specs


def abstract_train_state(cfg: ArchConfig, tcfg: TrainConfig, mesh):
    """ShapeDtypeStruct train state with attached shardings — the dry-run
    lowers against this, allocating nothing."""
    from jax.sharding import NamedSharding
    dp = dp_axes_of(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    n_model = mesh.shape["model"]
    specs = make_train_state_specs(cfg, tcfg, mesh)
    params_shape = transformer.abstract_params(cfg)

    if tcfg.partition == "fsdp":
        plan = plan_fsdp_tree(cfg, tcfg, mesh)
        # params keep GLOBAL shapes (dp sharding is in the spec)
        pstruct = params_shape
        local = fsdp_local_shapes(params_shape, plan, n_dp)
        ostruct_local = jax.eval_shape(partial(opt.init, tcfg.optim), local)
        ostruct = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                ((n_dp,) + l.shape) if l.ndim > 0 else l.shape, l.dtype),
            ostruct_local)
        state = {"params": pstruct, "opt": ostruct,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
    else:
        axes = train_axes_of(mesh, tcfg)
        n_sync = int(np.prod([mesh.shape[a] for a in axes]))
        meta = zero1_meta(cfg, n_sync, tcfg, mesh)
        n_inner = 1 if tcfg.dp_only else n_model
        ostruct = zero1_lib.state_struct(tcfg.optim, meta, n_inner)
        state = {"params": params_shape, "opt": ostruct,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}

    def attach(st, spec):
        return jax.ShapeDtypeStruct(st.shape, st.dtype,
                                    sharding=NamedSharding(mesh, spec))

    return jax.tree.map(
        attach, state, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)), specs


# ---------------------------------------------------------------------------
# the step builders
# ---------------------------------------------------------------------------

def build_train_step(cfg: ArchConfig, tcfg: TrainConfig, mesh):
    """Returns ``step(state, batch) -> (state, metrics)`` (un-jitted; the
    launcher jits with shardings + donate)."""
    if tcfg.partition == "fsdp":
        return _build_fsdp_step(cfg, tcfg, mesh)
    return _build_zero1_step(cfg, tcfg, mesh)


def _microbatch_grads(loss_fn, params, batch, n_micro: int):
    """Gradient accumulation via remat'd scan-inside-the-loss.

    Two structural choices, both memory-critical at deepseek-v3 scale:
      * the microbatch scan lives INSIDE the differentiated function, so
        the scan transpose accumulates parameter cotangents into ONE buffer
        of the params' dtype — no explicit f32 accumulation tree (which
        alone is 2× params);
      * the microbatch body is itself ``jax.checkpoint``ed, so the layer-
        scan residuals of only ONE microbatch are live during backward
        (otherwise: 29 layers × hidden × n_micro ≈ 26 GB for v3).
    Accumulation precision is the param dtype (bf16); the downstream
    reduce-scatter and optimizer math run in f32.  Returns (loss, grads)."""
    if n_micro == 1:
        return jax.value_and_grad(loss_fn)(params, batch)
    b = batch["tokens"].shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mbs = {
        k: v.reshape((n_micro, b // n_micro) + v.shape[1:])
        for k, v in batch.items()
    }
    loss_r = jax.checkpoint(loss_fn)

    def total_loss(params):
        def body(acc, mb):
            return acc + loss_r(params, mb), None
        s, _ = jax.lax.scan(body, jnp.float32(0), mbs)
        return s / n_micro

    return jax.value_and_grad(total_loss)(params)


def _build_zero1_step(cfg: ArchConfig, tcfg: TrainConfig, mesh):
    dp = train_axes_of(mesh, tcfg)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    meta = zero1_meta(cfg, n_dp, tcfg, mesh)
    pspecs = train_param_specs(cfg, tcfg, mesh)
    # persistent wire schedule: compiled ONCE per step signature (bucket
    # meta + policy + sync axes) and replayed by every trace/step — the
    # sched-cache hit is what makes re-tracing cheap (paper §3.3).
    comm_plan = sched_compile.cached_zero1_plan(
        meta, policy=tcfg.policy, axis_name=tuple(dp), n_dev=n_dp)

    def loss_fn(params, mb):
        h = transformer.forward(params, mb, cfg, remat=tcfg.remat)
        return chunked_ce_loss(params, h, mb["labels"], cfg, tcfg.loss_chunk)

    def sync_and_update(params, grads, opt_state):
        """Gradient sync.  Standard mode: inner shard_map manualizes
        'model' so buckets are fully local.  dp_only: every axis is already
        manual in the outer region — call zero1 directly."""
        def body(params, grads, opt_local):
            st = zero1_lib.global_to_local(opt_local)
            new_p, new_st, flag, gnorm = zero1_lib.zero1_step(
                tcfg.optim, meta, params, grads, st,
                dp_axes=tuple(dp), policy=tcfg.policy,
                tensor_norm_axes=tuple(dp) if tcfg.dp_only else None,
                plan=comm_plan,
            )
            return new_p, zero1_lib.local_to_global(new_st), flag, gnorm

        if tcfg.dp_only:
            return body(params, grads, opt_state)
        ospec_in = jax.tree.map(
            lambda l: P(None, "model") if getattr(l, "ndim", 0) == 2 else P(),
            opt_state,
        )
        return jax.shard_map(
            body,
            in_specs=(pspecs, pspecs, ospec_in),
            out_specs=(pspecs, ospec_in, P(), P()),
            axis_names={"model"},
            check_vma=False,
        )(params, grads, opt_state)

    def grads_body(params, batch):
        loss, grads = _microbatch_grads(loss_fn, params, batch,
                                        tcfg.microbatches)
        # each DP rank's own (unreduced) gradient, on a leading dp axis
        return (jax.lax.pmean(loss, tuple(dp)),
                jax.tree.map(lambda g: g[None], grads))

    def update_body(state, grads, loss):
        params = state["params"]
        grads = jax.tree.map(lambda g: g[0], grads)
        new_params, new_opt, flag, gnorm = sync_and_update(
            params, grads, state["opt"])
        if tcfg.guard_overflow:
            keep = (flag == 0)
            new_params = jax.tree.map(
                lambda n, o: jnp.where(keep, n, o), new_params, params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(keep, n, o), new_opt, state["opt"])
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + jnp.where(flag == 0, 1, 0)
            if tcfg.guard_overflow else state["step"] + 1,
        }
        metrics = {"loss": loss, "gnorm": gnorm, "overflow": flag}
        return new_state, metrics

    state_specs = make_train_state_specs(cfg, tcfg, mesh)
    manual_specs = _manual_state_specs(state_specs, dp)
    dpax = dp if len(dp) > 1 else dp[0]
    grad_specs = jax.tree.map(lambda _: P(dpax), manual_specs["params"],
                              is_leaf=lambda x: isinstance(x, P))
    # two programs: the gradient program does not depend on the wire, so
    # every policy (and the raw retry step) runs the same executable, and
    # a compressed step differs from its raw twin only in the sync program.
    # In one program XLA tiles the backward's matmuls per program, and the
    # twins' gradients part in the last bits.
    grads_fn = jax.jit(jax.shard_map(
        grads_body, mesh=mesh,
        in_specs=(manual_specs["params"], _batch_specs_tree(cfg, dp)),
        out_specs=(P(), grad_specs), axis_names=set(dp), check_vma=False))
    update_fn = jax.jit(jax.shard_map(
        update_body, mesh=mesh,
        in_specs=(manual_specs, grad_specs, P()),
        out_specs=(manual_specs, {"loss": P(), "gnorm": P(), "overflow": P()}),
        axis_names=set(dp), check_vma=False), donate_argnums=(0,))

    def step(state, batch):
        loss, grads = grads_fn(state["params"], batch)
        return update_fn(state, grads, loss)

    return step, state_specs


def _manual_state_specs(state_specs, dp):
    """Project full specs onto the outer-manual axes (pod/data): the model
    axis stays auto, so outer in_specs mention only dp axes."""
    dpset = set(dp)

    def proj(spec):
        if not isinstance(spec, P):
            return spec
        entries = []
        for e in tuple(spec):
            if e is None:
                entries.append(None)
            elif isinstance(e, (tuple, list)):
                kept = tuple(x for x in e if x in dpset)
                entries.append(kept if kept else None)
            else:
                entries.append(e if e in dpset else None)
        return P(*entries)

    return jax.tree.map(proj, state_specs, is_leaf=lambda x: isinstance(x, P))


def _batch_specs_tree(cfg: ArchConfig, dp):
    ax = dp if len(dp) > 1 else dp[0]
    s = {"tokens": P(ax, None), "labels": P(ax, None)}
    if cfg.enc_dec:
        s["frames"] = P(ax, None, None)
    if cfg.frontend == "vision_stub":
        s["vision_embeds"] = P(ax, None, None)
    return s


def _build_fsdp_step(cfg: ArchConfig, tcfg: TrainConfig, mesh):
    dp = dp_axes_of(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    plan = plan_fsdp_tree(cfg, tcfg, mesh)
    pspecs = model_specs(cfg, mesh)

    n_model = mesh.shape["model"]

    def gather_leaf_tree(sub_params, sub_plan, sub_specs):
        """Gather FSDP-sharded leaves of a subtree (inside loss_fn).

        Each leaf's gather runs inside an inner shard_map that manualizes
        'model': the flatten/reshape inside the wire codec then operates on
        LOCAL arrays.  (Flattening an auto-model-sharded dim would force
        GSPMD to all-gather the leaf over 'model' — 16x memory and wire.)"""
        leaves, treedef = jax.tree_util.tree_flatten(sub_params)
        dims = treedef.flatten_up_to(sub_plan)
        specs = treedef.flatten_up_to(sub_specs)
        out = []
        for l, d, spec in zip(leaves, dims, specs):
            if d < 0:
                out.append(l)
                continue
            moved = jnp.moveaxis(l, d, -1)
            entries = list(tuple(spec)) + [None] * (l.ndim - len(tuple(spec)))
            entries.append(entries.pop(d))  # follow the moveaxis
            mspec = P(*entries)
            # local (per-model-shard) shape of the moved leaf
            lshape = list(moved.shape)
            for dim_i, e in enumerate(entries):
                if e is None:
                    continue
                names = (e,) if isinstance(e, str) else tuple(e)
                lshape[dim_i] //= int(np.prod([mesh.shape[a] for a in names]))
            # plan-driven gather: the wire schedule for this leaf signature
            # is compiled once and cached (sched); repeated layers/steps
            # replay it instead of re-deriving widths and gating
            gplan = sched_compile.cached_fsdp_gather_plan(
                tuple(lshape), jnp.dtype(moved.dtype).name, tuple(dp),
                policy=tcfg.policy, n_dev=n_dp)
            gfn = sched_executor.gather_from_plan(gplan)

            def body(lm, _gfn=gfn):
                full, _flag = _gfn(lm)
                return full

            full = jax.shard_map(body, in_specs=(mspec,), out_specs=mspec,
                                 axis_names={"model"}, check_vma=False)(moved)
            out.append(jnp.moveaxis(full, -1, d))
        return jax.tree_util.tree_unflatten(treedef, out)

    def loss_fn(params, mb):
        # gather top-level leaves once; block leaves per-scan-step via hook
        top = {k: v for k, v in params.items() if k != "blocks"}
        top_plan = {k: v for k, v in plan.items() if k != "blocks"}
        top_specs = {k: v for k, v in pspecs.items() if k != "blocks"}
        top_full = gather_leaf_tree(top, top_plan, top_specs)
        blocks_plan = plan["blocks"]
        blocks_specs = pspecs["blocks"]

        def bpf(layer_p, idx):
            if idx < 0:  # prefix layer: already gathered with top
                return layer_p
            # plan/specs for a scan-sliced leaf: computed on stacked shapes;
            # slicing removes dim 0 → shift dims by -1, drop leading entry
            lp = jax.tree.map(lambda d: d - 1 if d > 0 else -1,
                              blocks_plan[idx])
            ls = jax.tree.map(lambda s: P(*tuple(s)[1:]), blocks_specs[idx],
                              is_leaf=lambda x: isinstance(x, P))
            return gather_leaf_tree(layer_p, lp, ls)

        full_params = dict(top_full, blocks=params["blocks"])
        h = transformer.forward(full_params, mb, cfg, remat=tcfg.remat,
                                block_param_fn=bpf)
        loss = chunked_ce_loss(top_full, h, mb["labels"], cfg, tcfg.loss_chunk)
        # scale: gather's VJP sums over DP; global-mean loss needs 1/n_dp
        return loss / n_dp, loss

    def outer_body(state, batch):
        params = state["params"]

        def scaled_loss(p, mb):
            l, _ = loss_fn(p, mb)
            return l

        loss_scaled, grads = _microbatch_grads(
            scaled_loss, params, batch, tcfg.microbatches)
        # replicated (non-sharded) leaves: their cotangents are per-DP-shard
        # grads of (local_loss / n_dp); the global-mean gradient is the SUM
        # over shards.  Sharded leaves arrived already summed (gather VJP).
        from repro.core.compressed_collectives import psum_safe
        def fix_rep(g, d):
            return psum_safe(g, tuple(dp)) if d < 0 else g
        grads = jax.tree.map(fix_rep, grads, plan)
        # grad clip: shards are disjoint over dp; model handled by GSPMD auto
        sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                 for l in jax.tree_util.tree_leaves(grads))
        def shard_sq(g, d):
            return jnp.sum(jnp.square(g.astype(jnp.float32))) if d >= 0 else 0.0
        sq_shard = sum(jax.tree_util.tree_leaves(
            jax.tree.map(shard_sq, grads, plan)))
        sq_rep = sq - sq_shard
        gnorm = jnp.sqrt(jax.lax.psum(sq_shard, tuple(dp)) + sq_rep)
        scale = jnp.minimum(1.0, tcfg.optim.grad_clip / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                        ).astype(g.dtype), grads)
        new_params, new_opt = opt.update(
            tcfg.optim, grads, _opt_local(state["opt"]), params)
        new_state = {
            "params": new_params,
            "opt": _opt_global(new_opt),
            "step": state["step"] + 1,
        }
        metrics = {
            "loss": jax.lax.pmean(loss_scaled * n_dp, tuple(dp)),
            "gnorm": gnorm,
            "overflow": jnp.int32(0),
        }
        return new_state, metrics

    state_specs = make_train_state_specs(cfg, tcfg, mesh)
    batch_spec = _batch_specs_tree(cfg, dp)

    def step(state, batch):
        return jax.shard_map(
            outer_body, mesh=mesh,
            in_specs=(_manual_state_specs(state_specs, dp), batch_spec),
            out_specs=(_manual_state_specs(state_specs, dp),
                       {"loss": P(), "gnorm": P(), "overflow": P()}),
            axis_names=set(dp), check_vma=False,
        )(state, batch)

    return step, state_specs


def _opt_local(opt_state):
    """Strip the leading (1,)-DP dim of the global FSDP state layout: in the
    manual region each device sees (1, ...local shard shape...)."""
    return jax.tree.map(lambda l: l if l.ndim == 0 else l[0], opt_state)


def _opt_global(opt_state):
    """Re-add the leading DP dim for the global layout (inverse of local)."""
    return jax.tree.map(lambda l: l if l.ndim == 0 else l[None], opt_state)


# ---------------------------------------------------------------------------
# weight-sync publish hook (src/repro/sync/): the trainer side of the RL
# weight-synchronization wire
# ---------------------------------------------------------------------------

def make_publish_hook(sync_engine, *, every: int = 1):
    """Bridge the train loop to a ``sync.WeightSyncEngine``.

    Returns ``hook(state) -> version | None``: call it after each
    optimizer step; every ``every`` steps it publishes ``state["params"]``
    as the next weight version (the step counter is read from the train
    state itself, so the cadence survives checkpoint restores).  The
    published tree's signature is step-stable, so every publish after the
    first hits the cached kind-"wsync" plan.  After restoring a trainer
    from a checkpoint, call ``sync_engine.advance_epoch()`` before the
    first publish — version numbers may repeat with different bits, and
    the epoch fence forces replicas back through a full send."""
    def hook(state):
        step = int(state["step"])
        if every > 1 and step % every != 0:
            return None
        return sync_engine.publish(state["params"])
    return hook
