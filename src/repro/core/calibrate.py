"""Width calibration for the static in-collective codec (paper §3.4).

The paper amortizes ANS-table metadata by observing that float tensor
distributions are *stable across training steps* (Fig. 12), transmitting the
table once and reusing it.  We push the same observation one level deeper:
the packed-width ``W`` and exception capacity are chosen *offline* (or on
the first steps) from observed exponent statistics, then baked into the
compiled step as static wire sizes.  Periodic revalidation detects drift;
the in-wire ``overflow`` flag catches violations exactly (packing.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec, packing


@dataclasses.dataclass(frozen=True)
class WidthChoice:
    width: int
    exc_frac: float
    est_exc_rate: float  # fraction of blocks expected to escape
    est_ratio: float  # predicted wire ratio vs raw
    entropy_bits: float  # ANS floor for reference


def block_range_stats(x: jax.Array, block: int = 512) -> jax.Array:
    """Per-block max code values under the zero-escape mapping (int32):
    ``max_nz - min_nz + 1`` over nonzero exponents (0 for all-zero blocks).
    A block packs losslessly at width W iff its stat < 2**W."""
    exp, _ = codec.split_planes(x)
    return _exp_range_stats(exp, block)


def _exp_range_stats(exp: jax.Array, block: int) -> jax.Array:
    """:func:`block_range_stats` of a uint8 exponent plane."""
    exp = packing._pad_to(exp, block)
    b = exp.reshape(-1, block)
    nz = b != 0
    base = jnp.min(jnp.where(nz, b, jnp.uint8(255)), axis=-1).astype(jnp.int32)
    mx = jnp.max(jnp.where(nz, b, jnp.uint8(0)), axis=-1).astype(jnp.int32)
    return jnp.where(jnp.any(nz, axis=-1), mx - base + 1, 0)


def width_cost_curve(
    x: jax.Array,
    *,
    block: int = 512,
    max_exc_frac: float = 0.02,
) -> tuple:
    """The full predicted cost curve: one :class:`WidthChoice` per candidate
    exponent width ``1..exp_bits`` (escape rate and wire ratio AT that
    width).  :func:`choose_width` picks from this curve.
    """
    exp, _ = codec.split_planes(x)
    lay = codec.layout_of(x.dtype)
    rngs = np.asarray(_exp_range_stats(exp, block))
    ent = float(codec.exponent_entropy_bits(exp, lay.exp_bits))
    n_blocks = len(rngs)
    cap = packing.exception_capacity(n_blocks, max_exc_frac)
    curve = []
    for w in range(1, lay.exp_bits + 1):
        ratio = (
            lay.lo_bits
            + w
            + 8.0 / block  # bases
            + (cap * (4 + block) * 8.0) / (n_blocks * block)  # exceptions
        ) / lay.total_bits
        curve.append(WidthChoice(
            width=w,
            exc_frac=max_exc_frac,
            est_exc_rate=float(np.mean(rngs >= (1 << w))),
            est_ratio=ratio,
            entropy_bits=ent,
        ))
    return tuple(curve)


def choose_width(
    x: jax.Array,
    *,
    block: int = 512,
    target_exc_rate: float = 1e-3,
    margin_bits: int = 0,
    max_exc_frac: float = 0.02,
) -> WidthChoice:
    """Smallest W such that the expected escape rate stays under target.

    ``margin_bits`` adds headroom for distribution drift between calibration
    and use (the paper's stability claim says drift is small; we don't rely
    on it for correctness, only for speed).
    """
    curve = width_cost_curve(x, block=block, max_exc_frac=max_exc_frac)
    for c in curve:
        if c.est_exc_rate <= target_exc_rate or c.width == curve[-1].width:
            return curve[min(c.width + margin_bits, curve[-1].width) - 1]
    raise AssertionError("unreachable: the last width always matches")


# elements per step of choose_delta_widths' statistics: its temporaries
# scale with this, not with the tree
_CAL_SLICE = 1 << 24


def choose_delta_widths(
    x: jax.Array, base: jax.Array, *, block: int = 512,
    target_exc_rate: float = 1e-3, max_exc_frac: float = 0.02,
) -> tuple:
    """Calibrate the XOR-delta wire's (exp_width, lo_width) from live data.

    ``x``/``base`` are two consecutive weight versions (or representative
    twins).  The exponent-delta width is :func:`choose_width`'s on the
    delta bit pattern: the smallest W at which at most ``target_exc_rate``
    of the blocks escape; the lo width is the smallest W whose per-ELEMENT
    escape rate stays under half the exception capacity (the lo packer
    escapes per element, not per block — the XOR carry tail is heavy but
    element-local).  The escape counts at every width are summed over
    slices of ``_CAL_SLICE`` elements, one jitted program each.  Store the
    result in ``CompressionProfile.widths["delta"/"delta_lo"]`` to drive
    ``CompressionPolicy.delta_widths``."""
    lay = codec.layout_of(x.dtype)
    x, base = x.reshape(-1), base.reshape(-1)
    n = x.shape[0]
    step = max(_CAL_SLICE // block, 1) * block  # slices hold whole blocks
    parts = jax.device_get([
        _delta_escapes(x, base, start, size=min(step, n - start), block=block)
        for start in range(0, n, step)])
    # summed on the host in int64: a slice's int32 counts cannot overflow,
    # a whole tree's might
    exp_esc, lo_esc = (np.sum([np.asarray(p[i], np.int64) for p in parts],
                              axis=0) for i in (0, 1))
    n_blocks = -(-n // block)
    w_exp = next((w for w in range(1, lay.exp_bits + 1)
                  if exp_esc[w - 1] / n_blocks <= target_exc_rate),
                 lay.exp_bits)
    budget = max_exc_frac / 2  # leave half the capacity as drift headroom
    w_lo = next((w for w in range(1, lay.lo_bits + 1)
                 if lo_esc[w - 1] / n <= budget), lay.lo_bits)
    return int(w_exp), int(w_lo)


@partial(jax.jit, static_argnames=("size", "block"))
def _delta_escapes(x, base, start, *, size: int, block: int):
    """Escapes of the delta of ``x[start:start + size]``: blocks whose
    exponent range needs more than W bits, and elements whose lo plane
    does, each for W = 1..bits of its plane (int32)."""
    lay = codec.layout_of(x.dtype)
    exp, lo = codec.split_bits(codec.xor_bits(
        jax.lax.dynamic_slice_in_dim(x, start, size),
        jax.lax.dynamic_slice_in_dim(base, start, size)), lay)
    rng = _exp_range_stats(exp, block)
    lo = lo.astype(jnp.uint32)
    return (jnp.stack([jnp.sum(rng >= (1 << w), dtype=jnp.int32)
                       for w in range(1, lay.exp_bits + 1)]),
            jnp.stack([jnp.sum(lo >= jnp.uint32(1 << w), dtype=jnp.int32)
                       for w in range(1, lay.lo_bits + 1)]))


@dataclasses.dataclass(frozen=True)
class CompressionProfile:
    """Calibrated parameters per tensor class, reusable across steps.

    Tensor classes follow the paper's Table 1: gradients / weights /
    activations have distinct but individually-stable distributions.
    """

    widths: dict  # class name -> width
    block: int = 512
    exc_frac: float = 0.02
    # extra exponent-width headroom for the all-gather phase of the two-shot
    # (the reduced-sum distribution); 0 = trust exceptions, calibratable.
    ag_extra_bits: int = 0

    @staticmethod
    def default(dtype_name: str = "bfloat16") -> "CompressionProfile":
        # Conservative defaults validated on normalized-tensor workloads;
        # per-run calibration (calibrate_tree) overrides them.
        base = {"bfloat16": 5, "float32": 5, "float16": 4,
                "float8_e4m3fn": 4, "float8_e5m2": 4}[dtype_name]
        return CompressionProfile(
            widths={"gradient": base, "weight": base, "activation": base}
        )

    def width_for(self, tensor_class: str) -> int:
        return self.widths.get(tensor_class, max(self.widths.values()))


def calibrate_tree(
    tree, *, tensor_class: str = "gradient", block: int = 512, **kw
) -> CompressionProfile:
    """Calibrate one width per tensor class from a pytree of live tensors
    (e.g. the first step's gradients)."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree) if hasattr(l, "dtype")]
    widths = [
        choose_width(l, block=block, **kw).width
        for l in leaves
        if jnp.dtype(l.dtype).name in codec.LAYOUTS
    ]
    w = max(widths) if widths else 8
    return CompressionProfile(widths={tensor_class: w}, block=block)
