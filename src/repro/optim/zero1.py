"""ZeRO-1 distributed optimizer fused with compressed two-shot collectives.

The paper's Fig. 9 shows the two-shot all-reduce (reduce-scatter + all-gather
with ONE encode/decode per phase) is the compression-friendly collective.
ZeRO-1 *is* a two-shot all-reduce with an optimizer update spliced between
the phases — so instead of bolting compression onto a black-box all-reduce,
we make the optimizer's natural RS/AG the compressed wire (DESIGN.md §8,
beyond-paper):

    grads --RS(compressed)--> grad shard --update--> param shard
          --AG(compressed)--> full params

Layout (inside the nested shard_map manual region; see train/step.py):
  * every *model shard* flattens its local (auto-model-sharded) param/grad
    leaves into per-dtype flat buckets — the paper's large-block granularity
    principle (Property 1) applied to the whole gradient pytree;
  * each bucket is padded to ``n_dp * block`` and divided into ``n_dp``
    shards; DP rank ``d`` owns shard ``d`` and its optimizer state
    (fp32 master + moments) — that state never leaves the device;
  * the RS wire carries gradient-class packed planes; the AG wire carries
    weight-class packed planes (distinct calibrated widths, paper Table 1).

State is stored globally as 2-D arrays ``(dp_total, model * shard_len)``
sharded ``P((pod, data), model)`` so the same arrays are addressable both by
GSPMD (checkpointing, init) and by the manual region (each device sees its
``(1, shard_len)`` slice).

The wire schedule (per-bucket compress-vs-raw gating, widths, fused
receive) is PLAN-DRIVEN: ``zero1_step`` executes a precompiled
``sched.CommPlan`` of kind "zero1" through ``sched.Zero1Execution``, which
also folds the step's wire accounting into one consolidated WireReport.
The step builder compiles the plan once per step signature
(``sched.compile.cached_zero1_plan``); calling ``zero1_step`` without a
plan compiles-and-caches on first sight (the planless thin wrapper).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.policy import CompressionPolicy
from repro.optim import optimizers as opt


def _axis_size(axes) -> Any:
    if isinstance(axes, (tuple, list)):
        return int(np.prod([jax.lax.axis_size(a) for a in axes]))
    return jax.lax.axis_size(axes)


def _dp_index(axes):
    if isinstance(axes, (tuple, list)):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(axes)


# ---------------------------------------------------------------------------
# bucket partitioning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BucketMeta:
    """Static description of the flat buckets for one local param tree."""

    dtype_names: tuple  # bucket order
    # per bucket: list of (flat_index_into_treedef, shape, size)
    members: tuple
    lengths: tuple  # unpadded length per bucket
    padded: tuple  # padded length per bucket (multiple of n_dp * block)
    n_dp: int
    block: int

    @property
    def shard_lens(self) -> tuple:
        return tuple(p // self.n_dp for p in self.padded)


def plan_buckets(params, n_dp: int, block: int = 512) -> BucketMeta:
    leaves = jax.tree_util.tree_leaves(params)
    groups: dict = {}
    for i, l in enumerate(leaves):
        name = jnp.dtype(l.dtype).name
        if name not in codec.LAYOUTS:
            name = "float32"  # reduce/update in f32; re-cast on unflatten
        groups.setdefault(name, []).append((i, tuple(l.shape), int(np.prod(l.shape))))
    names = tuple(sorted(groups))
    members = tuple(tuple(groups[n]) for n in names)
    lengths = tuple(sum(m[2] for m in groups[n]) for n in names)
    mult = n_dp * block
    padded = tuple(-(-L // mult) * mult for L in lengths)
    return BucketMeta(names, members, lengths, padded, n_dp, block)


def flatten_buckets(meta: BucketMeta, tree) -> list:
    leaves = jax.tree_util.tree_leaves(tree)
    out = []
    for name, mem, L, Lp in zip(meta.dtype_names, meta.members, meta.lengths,
                                meta.padded):
        dt = codec.LAYOUTS[name].dtype
        parts = [leaves[i].astype(dt).reshape(-1) for i, _, _ in mem]
        if Lp > L:
            parts.append(jnp.zeros((Lp - L,), dt))
        out.append(jnp.concatenate(parts) if len(parts) > 1 else parts[0])
    return out


def unflatten_buckets(meta: BucketMeta, buckets: list, like_tree):
    leaves = list(jax.tree_util.tree_leaves(like_tree))
    treedef = jax.tree_util.tree_structure(like_tree)
    for name, mem, bucket in zip(meta.dtype_names, meta.members, buckets):
        off = 0
        for i, shape, size in mem:
            leaves[i] = bucket[off : off + size].reshape(shape).astype(leaves[i].dtype)
            off += size
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# ZeRO-1 state + step (to be called inside the fully-manual region)
# ---------------------------------------------------------------------------

def zero1_init_local(ocfg: opt.OptimConfig, meta: BucketMeta, params,
                     dp_axes, dp_index=None) -> dict:
    """Build the local ZeRO-1 shard state inside the manual region.

    ``dp_index`` must be computed in the region where ``dp_axes`` are the
    *directly* manual axes and passed in (axis_index over a parent-manual
    axis cannot lower inside a nested shard_map)."""
    buckets = flatten_buckets(meta, params)
    idx = dp_index if dp_index is not None else _dp_index(dp_axes)
    state = {"count": jnp.zeros((), jnp.int32), "buckets": []}
    for bucket, sl in zip(buckets, meta.shard_lens):
        shard = jax.lax.dynamic_slice(bucket, (idx * sl,), (sl,))
        b = {"master": shard.astype(jnp.float32)}
        if ocfg.name == "adamw":
            b["m"] = jnp.zeros((sl,), jnp.float32)
            b["v"] = jnp.zeros((sl,), jnp.float32)
        else:  # adafactor on a flat shard degenerates to unfactored
            b["v"] = jnp.zeros((sl,), jnp.float32)
        state["buckets"].append(b)
    state["buckets"] = tuple(state["buckets"])
    return state


def zero1_step(
    ocfg: opt.OptimConfig,
    meta: BucketMeta,
    params,
    grads,
    state: dict,
    *,
    dp_axes,
    dp_index=None,
    model_axis: str = "model",
    policy: CompressionPolicy,
    tensor_norm_axes=None,
    plan=None,
):
    """One ZeRO-1 step.  ``grads`` are UNREDUCED over ``dp_axes`` (each DP
    rank's local-microbatch gradient); reduction happens in the compressed
    reduce-scatter.  Returns (new_params, new_state, overflow_flag).

    The wire schedule is plan-driven (``sched/``): ``plan`` is a precompiled
    ``CommPlan`` of kind "zero1" (the step builder compiles it once per step
    signature); ``plan=None`` is the planless thin wrapper — the plan is
    compiled on first sight and cached, so re-traces of the same signature
    replay the schedule instead of re-deriving the RS/AG gating and widths.
    Either way the executed primitives are identical to the historical
    planless path, bit-for-bit.
    """
    from repro import sched
    from repro.sched import compile as sched_compile

    n_dp = _axis_size(dp_axes)
    idx = dp_index if dp_index is not None else _dp_index(dp_axes)  # noqa: F841
    if plan is None:
        plan = sched_compile.cached_zero1_plan(
            meta, policy=policy, axis_name=dp_axes, n_dev=n_dp)
    gbuckets = flatten_buckets(meta, grads)
    flag = jnp.int32(0)
    c = state["count"] + 1
    lr = opt.lr_at(ocfg, c)

    with sched.Zero1Execution(plan, dp_axes) as ex:
        # -- reduce-scatter (compressed): grad shards -----------------------
        # fused receive (plan.fused <- policy.fused_decode_reduce): remote
        # packed chunks stream straight into the f32 grad-shard accumulator
        gshards = []
        norm_sq = jnp.float32(0)
        for i, (name, gb) in enumerate(zip(meta.dtype_names, gbuckets)):
            gs, f = ex.reduce_scatter(i, gb)
            # materialize the f32 shard whichever wire made it: XLA would
            # fuse the raw wire's bf16->f32 convert into the norm reduction
            # below, and a TPU reduction's summation order follows its
            # operand's storage dtype, so raw and compressed grad norms
            # would part in the last bits
            gs = jax.lax.optimization_barrier(gs)
            flag = jnp.maximum(flag, f)
            gs = gs / n_dp  # mean over DP
            gshards.append(gs)
            norm_sq = norm_sq + jnp.sum(jnp.square(gs))

        # global grad norm: shards are disjoint over dp AND model
        axes = tuple(dp_axes) if isinstance(dp_axes, (tuple, list)) else (dp_axes,)
        norm_axes = tensor_norm_axes or (axes + (model_axis,))
        gnorm = jnp.sqrt(jax.lax.psum(norm_sq, norm_axes))
        scale = jnp.minimum(1.0, ocfg.grad_clip / jnp.maximum(gnorm, 1e-12))

        # -- local shard update ---------------------------------------------
        new_buckets, new_state_buckets = [], []
        b1, b2 = ocfg.b1, ocfg.b2
        bc1 = 1 - b1 ** c.astype(jnp.float32)
        bc2 = 1 - b2 ** c.astype(jnp.float32)
        beta_af = 1.0 - c.astype(jnp.float32) ** (-ocfg.decay_rate)
        for i, (name, gs, bst) in enumerate(zip(meta.dtype_names, gshards,
                                                state["buckets"])):
            # the update reads materialized inputs and its outputs are
            # fenced from their consumers, so it compiles to the same code
            # whichever wire surrounds it: fused with its neighbours, XLA
            # rounds the same expression differently from program to program
            gs, bst, scale_i, lr_i, bc1_i, bc2_i, beta_i = (
                jax.lax.optimization_barrier(
                    (gs, bst, scale, lr, bc1, bc2, beta_af)))
            g = gs * scale_i
            master = bst["master"]
            if ocfg.name == "adamw":
                m = b1 * bst["m"] + (1 - b1) * g
                v = b2 * bst["v"] + (1 - b2) * jnp.square(g)
                upd = (m / bc1_i) / (jnp.sqrt(v / bc2_i) + ocfg.eps)
                nb = {"m": m, "v": v}
            else:
                v = beta_i * bst["v"] + (1 - beta_i) * (jnp.square(g) + 1e-30)
                upd = g / (jnp.sqrt(v) + 1e-12)
                rms = jnp.sqrt(jnp.mean(jnp.square(upd)) + 1e-30)
                upd = upd / jnp.maximum(1.0, rms)
                nb = {"v": v}
            nb["master"] = master - lr_i * (upd + ocfg.weight_decay * master)
            nb = jax.lax.optimization_barrier(nb)
            master = nb["master"]
            new_state_buckets.append(nb)

            # -- all-gather (compressed): redistribute updated params -------
            wire_dtype = codec.LAYOUTS[name].dtype
            shard_out = master.astype(wire_dtype)
            gathered, f = ex.all_gather(i, shard_out)
            flag = jnp.maximum(flag, f)
            new_buckets.append(gathered.reshape(-1))

    new_params = unflatten_buckets(meta, new_buckets, params)
    new_state = {"count": c, "buckets": tuple(new_state_buckets)}
    return new_params, new_state, flag, gnorm


def _raw_reduce_scatter(x, axes, n_dp):
    """Uncompressed RS as all_to_all + local f32 sum.

    Same wire bytes as a native reduce-scatter (each device sends n*(k-1)/k)
    and the same structure as the compressed path, so the roofline's
    raw-vs-compressed collective-byte comparison is apples-to-apples.  Also
    sidesteps XLA-CPU bf16-collective promotion (bitcast wire).  Accumulates
    in device-index order (``_seq_sum``) — the same order as the compressed
    fused/unfused paths, so compressed-vs-raw training is bit-comparable."""
    from repro.core.compressed_collectives import _seq_sum, raw_all_to_all
    # rows of 128 lanes where they fit: the TPU compiler spends minutes on
    # an all_to_all whose minor dimension is a whole multi-million-element
    # shard, and seconds on the same bytes as 128-lane rows
    lanes = (128,) if x.size % (n_dp * 128) == 0 else ()
    x2 = x.reshape((n_dp, -1) + lanes)
    ax = tuple(axes) if isinstance(axes, (tuple, list)) else axes
    recv = raw_all_to_all(x2, ax, 0, 0)
    return _seq_sum(recv, jnp.float32).reshape(-1)


def _raw_all_gather(x, axes):
    from repro.core.compressed_collectives import raw_all_gather
    ax = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    return raw_all_gather(x, ax, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# global (GSPMD-addressable) state representation for checkpoint/init
# ---------------------------------------------------------------------------

def state_struct(ocfg: opt.OptimConfig, meta: BucketMeta, n_model: int):
    """ShapeDtypeStructs for the global 2-D ZeRO-1 state arrays
    ``(dp_total, n_model * shard_len)``; P((pod, data), model)."""
    out = {"count": jax.ShapeDtypeStruct((), jnp.int32), "buckets": []}
    for sl in meta.shard_lens:
        b = {"master": jax.ShapeDtypeStruct((meta.n_dp, n_model * sl), jnp.float32)}
        if ocfg.name == "adamw":
            b["m"] = jax.ShapeDtypeStruct((meta.n_dp, n_model * sl), jnp.float32)
            b["v"] = jax.ShapeDtypeStruct((meta.n_dp, n_model * sl), jnp.float32)
        else:
            b["v"] = jax.ShapeDtypeStruct((meta.n_dp, n_model * sl), jnp.float32)
        out["buckets"].append(b)
    out["buckets"] = tuple(out["buckets"])
    return out


def local_to_global(state: dict) -> dict:
    """Reshape local (sl,) leaves to (1, sl) for the 2-D global layout."""
    return {
        "count": state["count"],
        "buckets": tuple(
            {k: v[None] for k, v in b.items()} for b in state["buckets"]
        ),
    }


def global_to_local(state: dict) -> dict:
    return {
        "count": state["count"],
        "buckets": tuple(
            {k: v.reshape(-1) for k, v in b.items()} for b in state["buckets"]
        ),
    }
